"""Fused compiled segment execution (repro.lower.fuse): every fused
segment matches the interpret oracle, the whole-net executable matches
layer-by-layer interpret, the process-wide executable cache serves
repeat executions with zero retrace, donation never touches weights,
and invalid plans still fail with the offending layer's name."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import solve
from repro.lower import fuse
from repro.lower import (lower_network, make_network_inputs,
                         measure_network, network_runner)
from repro.lower.calibrate import default_hw
from repro.lower.exec import input_extent
from repro.lower.fuse import (FusedNetwork, cache_stats, clear_cache,
                              compiled_plan_fn, fused_runner,
                              plan_signature)
from repro.obs.metrics import REGISTRY
from repro.workloads.layers import conv
from repro.workloads.nets import get_net, transformer

HW = default_hw()
TOL = 1e-5


def _plan(net):
    sched = solve(net, HW)
    assert sched.valid
    nplan = lower_network(sched, net, HW)
    assert nplan.executable, nplan.invalid_layers()
    return nplan


def _rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-12))


def _oracle(nplan, inputs):
    """Layer-by-layer interpret-mode outputs: the bit-accuracy oracle
    the fused tier is judged against."""
    return network_runner(nplan, inputs, backend="interpret")().outputs


# ---------------------------------------------------------------------------
# per-segment numerics vs the interpret oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: get_net("mlp", batch=4),
    lambda: transformer(batch=8, layers=2),
    lambda: get_net("alexnet", batch=1),
], ids=["mlp", "transformer2", "alexnet"])
def test_fused_segments_match_interpret_oracle(make):
    net = make()
    nplan = _plan(net)
    inputs = make_network_inputs(nplan, seed=0)
    oracle = _oracle(nplan, inputs)
    fused = fused_runner(nplan, cache=False)
    for index, (consumes, produces) in enumerate(fused.segment_io):
        assert produces, f"segment {index} produces nothing"
        # feed the segment from oracle boundary values, so each segment
        # is judged on its own (errors don't accumulate across segments)
        feed = {s: inputs[s] if s in inputs else oracle[s]
                for s in consumes}
        out = fused.run_segment(index, feed)
        assert set(out) == set(produces)
        for name in produces:
            err = _rel_err(out[name], oracle[name])
            assert err < TOL, f"{net.name} segment {index} " \
                              f"layer {name}: rel err {err:.2e}"


def test_whole_network_fused_matches_oracle():
    nplan = _plan(get_net("mlp", batch=4))
    inputs = make_network_inputs(nplan, seed=0)
    oracle = _oracle(nplan, inputs)
    fused = fused_runner(nplan, cache=False)
    out = fused(inputs, keep="all")
    assert set(out) == set(nplan.order)
    for name in nplan.order:
        assert _rel_err(out[name], oracle[name]) < TOL, name
    # the serving variant returns only boundary/network outputs —
    # forwarded in-segment tensors never materialize
    boundary = fused(inputs, keep="boundary")
    assert set(boundary) < set(nplan.order)
    fwd = set(nplan.forwarded())
    kept_fwd = {n for s in fused.segment_io for n in s[1]} & fwd
    assert set(boundary) & fwd <= kept_fwd
    for name in boundary:
        assert _rel_err(boundary[name], oracle[name]) < TOL, name


def test_network_runner_compiled_backend():
    nplan = _plan(get_net("mlp", batch=4))
    inputs = make_network_inputs(nplan, seed=0)
    oracle = _oracle(nplan, inputs)
    ex = network_runner(nplan, inputs, backend="compiled")()
    assert ex.backend == "compiled"
    assert set(ex.forwarded) == set(nplan.forwarded())
    for name, val in ex.outputs.items():
        assert _rel_err(val, oracle[name]) < TOL, name
    assert measure_network(nplan, inputs, iters=1, warmup=1,
                           backend="compiled") > 0


def test_oracle_tolerance_fails_a_dropped_filter_tap(monkeypatch):
    """``ORACLE_TOL`` is tight enough to catch a wrong kernel: the
    compiled conv with one filter tap dropped fails the comparison that
    the right conv passes."""
    from repro.lower import fuse
    from repro.lower.exec import ORACLE_TOL
    from repro.lower.netexec import NetworkExecution, compare_network
    nplan = _plan(get_net("alexnet", batch=1))
    inputs = make_network_inputs(nplan, seed=0)

    def verify():
        out = FusedNetwork(nplan)(inputs, keep="all")   # fresh trace
        ex = NetworkExecution(outputs=out, forwarded=(), roundtrips=(),
                              seconds=0.0, backend="compiled")
        return compare_network(nplan, ex, inputs)

    right = verify()
    assert right.ok and right.max_rel_err < ORACLE_TOL
    conv = fuse._conv
    monkeypatch.setattr(fuse, "_conv", lambda plan, x, w: conv(
        plan, x, w.at[:, :, 0, 0].set(0.0)))
    wrong = verify()
    assert not wrong.ok and wrong.max_rel_err > 10 * ORACLE_TOL
    assert nplan.plans[wrong.worst_layer].kind == "conv"


# ---------------------------------------------------------------------------
# the compiled conv: one windowed XLA convolution per layer
# ---------------------------------------------------------------------------

CONV_CASES = [(1, 1, 1, 64), (1, 1, 2, 64), (3, 3, 1, 16), (3, 3, 2, 16),
              (7, 7, 2, 3), (11, 11, 4, 3)]


def _conv_case(R, S, stride, C, N=2, K=8, X=5, Y=4):
    """A conv layer's plan stand-in (``_conv`` reads only ``plan.layer``)
    and seeded operands at the layer's adapted input extent."""
    layer = conv("c", N, C, K, X, Y, R, S, stride=stride)
    XI, YI = input_extent(layer)
    kx, kw = jax.random.split(jax.random.PRNGKey(R * 100 + stride * 10 + C))
    x = jax.random.normal(kx, (N, C, XI, YI), jnp.float32)
    w = jax.random.normal(kw, (K, C, R, S), jnp.float32)
    return SimpleNamespace(layer=layer), x, w


@pytest.mark.parametrize("R,S,stride,C", CONV_CASES,
                         ids=[f"{r}x{s}s{st}c{c}"
                              for r, s, st, c in CONV_CASES])
def test_compiled_conv_matches_a_per_tap_formula(R, S, stride, C):
    """``fuse._conv`` against the tap loop it replaced: a strided slice
    of the input per filter tap, contracted with that tap's [K, C]
    weights and summed, in float64."""
    plan, x, w = _conv_case(R, S, stride, C)
    X, Y = plan.layer.dim("X"), plan.layer.dim("Y")
    xs, ws = np.asarray(x, np.float64), np.asarray(w, np.float64)
    want = np.zeros((x.shape[0], w.shape[0], X, Y))
    for r in range(R):
        for s in range(S):
            patch = xs[:, :, r:r + (X - 1) * stride + 1:stride,
                       s:s + (Y - 1) * stride + 1:stride]
            want += np.einsum("ncxy,kc->nkxy", patch, ws[:, :, r, s])
    got = fuse._conv(plan, x, w)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel_err(got, want) < TOL


def test_compiled_conv_is_one_windowed_convolution():
    """The 7x7 stride-2 stem is one ``conv_general_dilated`` and no
    per-tap slice; an input off the layer's extent fails at trace time."""
    plan, x, w = _conv_case(7, 7, 2, 3)
    prims = [e.primitive.name for e in jax.make_jaxpr(
        lambda a, b: fuse._conv(plan, a, b))(x, w).jaxpr.eqns]
    assert prims.count("conv_general_dilated") == 1
    assert "slice" not in prims
    with pytest.raises(ValueError, match="the layer wants"):
        fuse._conv(plan, x[:, :, 1:, 1:], w)


# ---------------------------------------------------------------------------
# the executable cache: hit on re-execution, zero retrace
# ---------------------------------------------------------------------------

def test_executable_cache_hits_with_zero_retrace():
    clear_cache()
    net = get_net("mlp", batch=4)
    nplan = _plan(net)
    inputs = make_network_inputs(nplan, seed=0)
    hits = REGISTRY.get("fused_cache_events_total")
    h0, m0 = hits.value(event="hit"), hits.value(event="miss")

    fused = fused_runner(nplan)
    assert cache_stats()["misses"] == 1
    assert hits.value(event="miss") == m0 + 1
    fused(inputs, keep="boundary")
    traces = fused.traces
    assert traces >= 1

    # a fresh lowering of the same schedule has the same signature:
    # the second "execution" of the plan reuses the traced executable
    nplan2 = _plan(net)
    assert plan_signature(nplan2) == plan_signature(nplan)
    fused2 = fused_runner(nplan2)
    assert fused2 is fused                    # same executable object
    assert hits.value(event="hit") == h0 + 1
    fused2(make_network_inputs(nplan2, seed=1), keep="boundary")
    assert fused2.traces == traces            # zero retrace on re-execution

    # a different plan (different batch -> different shapes) is a miss
    other = _plan(get_net("mlp", batch=8))
    assert plan_signature(other) != plan_signature(nplan)
    assert fused_runner(other) is not fused
    assert cache_stats()["misses"] == 2
    clear_cache()
    assert cache_stats() == {"size": 0, "hits": 0, "misses": 0,
                             "evictions": 0}


# ---------------------------------------------------------------------------
# donation: activations donatable, weights never
# ---------------------------------------------------------------------------

@pytest.mark.filterwarnings("ignore:Some donated buffers")
def test_donated_buffers_are_safe():
    # (on CPU donation is a no-op — jax warns and keeps the buffers —
    # so this asserts the semantics survive wherever donation lands)
    nplan = _plan(get_net("mlp", batch=4))
    inputs = make_network_inputs(nplan, seed=0)
    fused = fused_runner(nplan, cache=False)
    expect = jax.device_get(fused(inputs, keep="boundary"))

    donated = fused({k: jnp.array(v) for k, v in inputs.items()},
                    keep="boundary", donate=True)
    for name, val in expect.items():
        assert _rel_err(donated[name], val) < TOL, name
    # weights are never donated: the same resident weight arrays serve
    # the next request (only activations were handed over)
    again = fused({k: (v if k.endswith(".W") else jnp.array(v))
                   for k, v in inputs.items()}, keep="boundary",
                  donate=True)
    for name, val in expect.items():
        assert _rel_err(again[name], val) < TOL, name


# ---------------------------------------------------------------------------
# invalid plans fail loudly, naming the layer
# ---------------------------------------------------------------------------

def test_invalid_plan_errors_name_layer():
    net = get_net("mobilenet", batch=1)       # dwconv has no kernel
    sched = solve(net, HW)
    nplan = lower_network(sched, net, HW)
    assert not nplan.executable
    with pytest.raises(ValueError, match="mobilenet.*dw"):
        fused_runner(nplan, cache=False)
    with pytest.raises(ValueError, match="mobilenet.*dw"):
        FusedNetwork(nplan)
    inputs = {}
    with pytest.raises(ValueError, match="mobilenet.*dw"):
        network_runner(nplan, inputs, backend="compiled")
    bad = next(p for _, p in sorted(nplan.plans.items()) if not p.valid)
    with pytest.raises(ValueError, match=bad.layer.name):
        compiled_plan_fn(bad)


# ---------------------------------------------------------------------------
# per-backend calibration storage
# ---------------------------------------------------------------------------

def test_per_backend_calibration_registry():
    from repro.core.cost_model import (Calibration, get_calibration,
                                       set_calibration)
    try:
        cal_i = Calibration(a_compute=1.0, backend="interpret")
        cal_c = Calibration(a_compute=2.0, backend="compiled")
        set_calibration(cal_i)
        set_calibration(cal_c)
        # the last-installed backend is active; both stay addressable
        assert get_calibration() is cal_c
        assert get_calibration("interpret") is cal_i
        assert get_calibration("compiled") is cal_c
        set_calibration(None, backend="compiled")
        assert get_calibration("compiled") is None
        assert get_calibration("interpret") is cal_i
    finally:
        set_calibration(None)
    assert get_calibration() is None
