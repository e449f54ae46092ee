"""Network-tier lowering/execution: NetworkPlan structure, the buffer
schedule (on-chip forwarding vs host round-trips), whole-graph numerics
vs the reference pass, adapters, and the network calibration record."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import solve
from repro.core.solver.kapla import NetworkSchedule
from repro.lower import (execute_network, lower_network,
                         make_network_inputs, verify_network)
from repro.lower.calibrate import default_hw, run_network_calibration
from repro.lower.netexec import (adapt_tensor, _eltwise_operands,
                                 required_input_shape)
from repro.workloads.nets import get_net, transformer

HW = default_hw()


def _plan(net):
    sched = solve(net, HW)
    assert sched.valid
    return sched, sched.lower(net, HW)


# ---------------------------------------------------------------------------
# plan structure + buffer schedule
# ---------------------------------------------------------------------------

def test_network_plan_structure_mlp():
    net = get_net("mlp", batch=4)
    sched, nplan = _plan(net)
    assert nplan.executable, nplan.invalid_layers()
    assert nplan.order == tuple(l.name for l in net.layers)
    assert set(nplan.plans) == set(nplan.order) == set(nplan.placements)
    # segments mirror the solved chain exactly
    assert [(-s.start + s.stop) for s in nplan.segments] == \
        [seg.stop - seg.start for seg in sched.chain.segments]
    for seg in nplan.segments:
        assert seg.layer_names == nplan.order[seg.start:seg.stop]
    # every placement is self-consistent
    for name, p in nplan.placements.items():
        assert p.producer == name
        if p.forwarded:
            seg = nplan.segment_of(name)
            assert seg.length > 1
            assert all(c in seg.layer_names for c in p.consumers)
            assert p.granule_bytes <= p.spare_bytes
        else:
            assert p.reason
    assert nplan.predicted_latency_cycles == sched.total_latency_cycles


def test_forwarded_tensors_skip_host_roundtrip():
    net = get_net("mlp", batch=4)
    _, nplan = _plan(net)
    fwd = nplan.forwarded()
    assert fwd, "mlp chain should keep at least one tensor on-chip"
    ex = execute_network(nplan)
    assert set(ex.forwarded) == set(fwd)
    assert not set(ex.forwarded) & set(ex.roundtrips)
    assert set(ex.forwarded) | set(ex.roundtrips) == set(nplan.order)
    # on-chip handoffs stayed live jax arrays end to end
    for n in ex.forwarded:
        assert isinstance(ex.outputs[n], jnp.ndarray)


def test_network_plan_reports_unsupported_layers():
    net = get_net("mobilenet", batch=1)       # dwconv has no kernel yet
    sched = solve(net, HW)
    nplan = lower_network(sched, net, HW)
    bad = dict(nplan.invalid_layers())
    assert not nplan.executable
    assert any("dwconv" in r for r in bad.values())
    with pytest.raises(ValueError, match="mobilenet.*dw"):
        execute_network(nplan)


def test_mixed_external_sources_are_refused():
    # a layer fed by both an in-graph producer and an external name would
    # silently drop the external operand — the plan must refuse it loudly
    from repro.workloads.layers import LayerGraph, eltwise, fc
    net = LayerGraph("mixed", [
        fc("a", 4, 32, 32),
        eltwise("m", 4, 32, 1, 1, src=["a", "external"]),
    ])
    sched = solve(net, HW)
    nplan = lower_network(sched, net, HW)
    bad = dict(nplan.invalid_layers())
    assert "m" in bad and "external" in bad["m"]
    with pytest.raises(ValueError, match="mix of in-graph and external"):
        execute_network(nplan)


def test_lower_from_deserialized_schedule():
    net = get_net("mlp", batch=4)
    sched, nplan = _plan(net)
    back = NetworkSchedule.from_json(json.loads(json.dumps(sched.to_json())),
                                     graph=net)
    nplan2 = lower_network(back, net, HW)
    assert nplan2.executable
    assert [s.layer_names for s in nplan2.segments] == \
        [s.layer_names for s in nplan.segments]
    assert nplan2.forwarded() == nplan.forwarded()
    # without a chain, lowering degrades to singleton segments (no pipelining)
    back.chain = None
    nplan3 = lower_network(back, net, HW)
    assert nplan3.executable
    assert len(nplan3.segments) == len(net.layers)
    assert not nplan3.forwarded()


# ---------------------------------------------------------------------------
# end-to-end numerics vs the whole-graph reference pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: get_net("mlp", batch=4),
    lambda: transformer(batch=8, layers=2),
    lambda: get_net("lstm", batch=8),
], ids=["mlp", "transformer2", "lstm"])
def test_network_executes_against_reference(make):
    net = make()
    _, nplan = _plan(net)
    assert nplan.executable, nplan.invalid_layers()
    ver = verify_network(nplan)
    assert ver.ok, f"{net.name}: {ver.worst_layer} err {ver.max_rel_err:.2e}"
    assert set(ver.errors) == set(nplan.order)
    assert ver.n_forwarded >= 1


def test_alexnet_executes_end_to_end():
    # the acceptance workload: conv + pool + fc through one pipeline, with
    # at least one multi-layer segment forwarding on-chip
    net = get_net("alexnet", batch=1)
    _, nplan = _plan(net)
    assert nplan.executable, nplan.invalid_layers()
    assert any(s.length > 1 for s in nplan.segments)
    ver = verify_network(nplan, tol=1e-3)
    assert ver.ok, f"{ver.worst_layer} err {ver.max_rel_err:.2e}"
    assert ver.n_forwarded >= 1


def test_measure_network_and_runner_reuse():
    from repro.lower import measure_network, network_runner
    net = get_net("mlp", batch=4)
    _, nplan = _plan(net)
    assert measure_network(nplan, iters=1) > 0
    # a pre-warmed runner is reused without re-compiling (warmup=0)
    inputs = make_network_inputs(nplan)
    run = network_runner(nplan, inputs)
    run()
    assert measure_network(nplan, iters=1, warmup=0, runner=run) > 0


def _reduction_outer_plan():
    """A one-fc network plan whose DRAM nest puts the reduction axis
    outermost: interpret mode runs it, compiled Pallas must refuse it."""
    from repro.core.solver.intralayer import Constraints, solve_intra_layer
    from repro.lower import lower_scheme
    from repro.lower.netplan import NetworkPlan, SegmentPlan, TensorPlacement
    from repro.workloads.layers import fc
    layer = fc("g.fc", 128, 1024, 1024)
    scheme, cost = solve_intra_layer(layer, HW,
                                     Constraints(nodes=HW.node_array))
    assert scheme is not None and cost.valid
    scheme.levels[-1].order = ("C", "K", "N", "X", "Y")   # reduction outer
    plan = lower_scheme(scheme, HW)
    assert plan.valid and plan.grid[0].dim == "C" and len(plan.grid) > 1
    nplan = NetworkPlan(
        graph_name="g", order=("g.fc",), plans={"g.fc": plan},
        segments=(SegmentPlan(0, 0, 1, ("g.fc",), ((1, 1),), 1.0),),
        placements={"g.fc": TensorPlacement("g.fc", (), 0, False,
                                            reason="network output")},
        predicted_latency_cycles=0.0, predicted_energy_pj=0.0)
    return nplan


def test_compiled_mode_applies_revisit_guard():
    # compiled Pallas cannot accumulate across non-consecutive output-block
    # revisits; the network runner must enforce the layer tier's guard
    from repro.lower import network_runner
    nplan = _reduction_outer_plan()
    inputs = make_network_inputs(nplan)
    assert network_runner(nplan, inputs, backend="interpret") is not None
    with pytest.raises(ValueError, match="reduction grid axes innermost"):
        network_runner(nplan, inputs, backend="pallas")


def test_mesh_tasks_apply_revisit_guard():
    # the mesh executor's per-layer tasks build on the same steps as the
    # network runner, so compiled Pallas refuses the same plan there
    from repro.lower.meshexec import build_segment_tasks
    nplan = _reduction_outer_plan()
    weights = make_network_inputs(nplan)
    assert len(build_segment_tasks(nplan, weights,
                                   backend="interpret")) == 1
    with pytest.raises(ValueError, match="reduction grid axes innermost"):
        build_segment_tasks(nplan, weights, backend="pallas")


# ---------------------------------------------------------------------------
# the canonical adapter
# ---------------------------------------------------------------------------

def test_adapt_tensor_rules():
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 2, 2)
    # rule 1: equal size -> reshape (flatten before FC)
    flat = adapt_tensor(x, (2, 12))
    assert flat.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(flat),
                                  np.asarray(x).reshape(2, 12))
    # rule 2: channel-matched spatial pad (conv halo) is centered zeros
    pad = adapt_tensor(x, (2, 3, 4, 4))
    assert pad.shape == (2, 3, 4, 4)
    np.testing.assert_array_equal(np.asarray(pad[:, :, 1:3, 1:3]),
                                  np.asarray(x))
    assert float(jnp.abs(pad[:, :, 0]).sum()) == 0.0
    # ... and crop inverts it
    np.testing.assert_array_equal(np.asarray(adapt_tensor(pad, x.shape)),
                                  np.asarray(x))
    # rule 3: divisible size -> fold-sum (LSTM gate merge)
    y = jnp.ones((2, 8), jnp.float32)
    fold = adapt_tensor(y, (2, 2, 1, 1))
    assert fold.shape == (2, 2, 1, 1)
    np.testing.assert_allclose(np.asarray(fold), 4.0)
    with pytest.raises(ValueError, match="cannot adapt"):
        adapt_tensor(jnp.ones((2, 5)), (2, 3))


def test_eltwise_concat_embedding():
    from repro.workloads.layers import eltwise
    layer = eltwise("cat", 2, 6, 4, 4, src=["a", "b"])
    a = jnp.ones((2, 2, 4, 4), jnp.float32)
    b = 2 * jnp.ones((2, 4, 4, 4), jnp.float32)
    ops = _eltwise_operands([a, b], layer)
    assert all(o.shape == required_input_shape(layer) for o in ops)
    total = np.asarray(sum(ops))
    np.testing.assert_allclose(total[:, :2], 1.0)   # a's channels
    np.testing.assert_allclose(total[:, 2:], 2.0)   # b's channels


# ---------------------------------------------------------------------------
# network calibration record
# ---------------------------------------------------------------------------

def test_network_calibration_skipped_numerics_stay_visible():
    # a net excluded from the timing record for numerics must still carry
    # its rel error, so the bench's --max-network-rel-err gate can fire
    rec = run_network_calibration(HW, quick=True, iters=1, tol=0.0,
                                  nets=[get_net("mlp", batch=4)])
    assert rec["n_nets"] == 0
    assert rec["skipped"] and all("max_rel_err" in s
                                  for s in rec["skipped"])


def test_network_calibration_record_quick():
    rec = run_network_calibration(HW, quick=True, iters=1)
    assert rec["n_nets"] >= 2, rec["skipped"]
    for e in rec["nets"]:
        assert e["max_rel_err"] < 1e-3
        assert e["measured_seconds"] > 0
        assert e["n_forwarded"] >= 1
        assert e["predicted_cycles"] > 0
    assert "spearman_network" in rec
    json.dumps(rec)                       # record is JSON-safe


# ---------------------------------------------------------------------------
# prune_stats JSON round-trip (regression: silently dropped before)
# ---------------------------------------------------------------------------

def test_network_schedule_json_preserves_prune_stats():
    net = get_net("mlp", batch=8)
    sched = solve(net, HW)
    assert sched.prune_stats is not None and sched.prune_stats.total > 0
    back = NetworkSchedule.from_json(json.loads(json.dumps(sched.to_json())),
                                     graph=net)
    assert back.prune_stats is not None
    assert back.prune_stats == sched.prune_stats
    # absent field (older records) still deserializes
    d = sched.to_json()
    del d["prune_stats"]
    assert NetworkSchedule.from_json(d, graph=net).prune_stats is None
