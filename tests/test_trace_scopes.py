"""The served device path instrumented from inside the program: each
layer's ops named by a ``jax.named_scope`` and mapped back from the
compiled module (``FusedNetwork.op_layers``), the per-call host spans of
``netexec.run_fused``, ``obs.trace`` mirrored into the JAX profiler, and
``host.gc`` spans around garbage collections."""
import gc
import re

import jax
import pytest

from repro.core.solver import solve
from repro.lower import lower_network, make_network_inputs, network_runner
from repro.lower.calibrate import default_hw
from repro.lower.fuse import fused_runner, hlo_op_layers, input_specs
from repro.obs import trace
from repro.workloads.layers import LayerGraph, conv, eltwise, fc, pool

HW = default_hw()


@pytest.fixture(autouse=True)
def _tracing_off():
    trace.disable()
    yield
    trace.disable()


def _tiny_graph(n: int = 2) -> LayerGraph:
    """Each kind the fused tier runs: a strided conv, a padded pool, a
    bottleneck with a projection and an add, a global pool and an fc."""
    return LayerGraph("scoped", [
        conv("conv1", n, 3, 8, 16, 16, 3, 3, stride=2),
        pool("pool1", n, 8, 8, 8, 3, 3, src=["conv1"]),
        conv("b.a", n, 8, 4, 8, 8, 1, 1, src=["pool1"]),
        conv("b.b", n, 4, 4, 8, 8, 3, 3, src=["b.a"]),
        conv("b.c", n, 4, 16, 8, 8, 1, 1, src=["b.b"]),
        conv("b.p", n, 8, 16, 8, 8, 1, 1, src=["pool1"]),
        eltwise("b.add", n, 16, 8, 8, src=["b.c", "b.p"]),
        pool("gap", n, 16, 1, 1, 8, 8, stride=8, src=["b.add"]),
        fc("fc", n, 16, 10, src=["gap"]),
    ])


@pytest.fixture(scope="module")
def tiny_plan():
    graph = _tiny_graph()
    sched = solve(graph, HW)
    assert sched.valid
    nplan = lower_network(sched, graph, HW)
    assert nplan.executable, nplan.invalid_layers()
    return nplan


def _run_n(nplan, calls: int):
    run = network_runner(nplan, make_network_inputs(nplan, seed=0),
                         backend="compiled", keep="boundary")
    for _ in range(calls):
        run()


# ---------------------------------------------------------------------------
# layer scopes -> instruction map
# ---------------------------------------------------------------------------

def test_op_layers_covers_every_layer_and_nothing_else(tiny_plan):
    fused = fused_runner(tiny_plan, cache=False)
    ops = fused.op_layers("boundary")
    assert set(ops.values()) == set(tiny_plan.order)
    kinds = {tiny_plan.plans[n].kind for n in ops.values()}
    assert kinds == {"conv", "pool", "eltwise", "fc"}
    assert not any(k.startswith("parameter") or k.startswith("acts")
                   or k.startswith("weights") for k in ops)


def test_op_layers_maps_the_convolution_to_its_layer(tiny_plan):
    """Each conv is one ``convolution`` instruction of the compiled
    module, named back to its layer: the strided 3x3 stem to ``conv1``."""
    fused = fused_runner(tiny_plan, cache=False)
    ops = fused.op_layers("boundary")
    specs = input_specs(tiny_plan)
    text = fused._fn(("net", "boundary", False)).lower(
        {k: v for k, v in specs.items() if not k.endswith(".W")},
        {k: v for k, v in specs.items() if k.endswith(".W")},
    ).compile().as_text()
    convs = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*\S+\s+"
                           r"convolution\(", text, re.M))
    assert "conv1" in {ops.get(c) for c in convs}


def test_hlo_op_layers_reads_entry_scopes_only():
    text = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %inner = f32[4] negate(%p), metadata={op_name="jit(fn)/a/neg"}',
        "}",
        "",
        "ENTRY %main.5 (acts: f32[4]) -> f32[4] {",
        '  %acts = f32[4] parameter(0), '
        'metadata={op_name="acts[\\\'a.I\\\']"}',
        '  %slice_maximum_fusion.1 = f32[4] fusion(%acts), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(fn)/pool1/max" '
        "stack_frame_id=14}",
        '  %pad.2 = f32[6] pad(%acts), '
        'metadata={op_name="jit(fn)/b.a/jit(_pad)/pad"}',
        "  %copy-start = (f32[4], f32[4], u32[]) copy-start(%acts)",
        '  ROOT %dot.3 = f32[4] dot(%pad.2, %acts), '
        'metadata={op_name="jit(fn)/fc/dot_general"}',
        "}",
    ])
    assert hlo_op_layers(text, ["a", "pool1", "b.a", "fc"]) == {
        "slice_maximum_fusion.1": "pool1", "pad.2": "b.a", "dot.3": "fc"}


# ---------------------------------------------------------------------------
# per-call host spans
# ---------------------------------------------------------------------------

def test_compile_span_once_per_variant_then_dispatch(tiny_plan):
    fused = fused_runner(tiny_plan, cache=False)
    inputs = make_network_inputs(tiny_plan, seed=0)
    t = trace.enable()
    for _ in range(3):
        fused(inputs, keep="boundary")
    for _ in range(2):
        fused(inputs, keep="all")
    trace.disable()
    compiles = t.find("fuse.compile")
    assert len(compiles) == 2
    assert all(e["args"]["net"] == "scoped" for e in compiles)
    assert len(t.find("fuse.dispatch")) == 3
    assert len(t.find("fuse.feed")) == 5


def test_served_call_spans_nest_in_order(tiny_plan):
    t = trace.enable()
    _run_n(tiny_plan, 3)
    trace.disable()
    runs = t.find("netexec.run")
    assert len(runs) == 3
    for run in runs:
        lo, hi = run["ts"], run["ts"] + run["dur"]
        inner = sorted((e for e in t.events if e["name"] in (
            "fuse.feed", "fuse.compile", "fuse.dispatch", "netexec.wait")
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi),
            key=lambda e: e["ts"])
        assert [e["name"] for e in inner][0] == "fuse.feed"
        assert [e["name"] for e in inner][-1] == "netexec.wait"
        assert len(inner) == 3


def test_mirrored_spans_land_on_the_profiler_host_plane(tiny_plan,
                                                        tmp_path):
    from jax.profiler import ProfileData
    _run_n(tiny_plan, 1)                  # compile outside the session
    trace.enable(trace.Tracer(profiler=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_n(tiny_plan, 2)
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    names = ("netexec.run", "fuse.feed", "fuse.dispatch", "netexec.wait")
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name in names]
    runs = sorted(s for s in spans if s[0] == "netexec.run")
    assert len(runs) >= 2      # the runner's first call compiles here too
    for _, lo, hi in runs:
        inner = sorted((s for s in spans if s[0] != "netexec.run"
                        and lo <= s[1] and s[2] <= hi),
                       key=lambda s: s[1])
        assert [s[0] for s in inner] == list(names[1:])
        for (_, _, end), (_, start, _) in zip(inner, inner[1:]):
            assert end <= start


# ---------------------------------------------------------------------------
# the tracer's switches
# ---------------------------------------------------------------------------

def test_disabled_tracer_hands_out_noop_and_unhooks_gc():
    t = trace.enable(trace.Tracer())
    assert t._on_gc in gc.callbacks
    trace.enable(trace.Tracer())           # replaced: the first unhooked
    assert t._on_gc not in gc.callbacks
    t = trace.disable()
    assert t._on_gc not in gc.callbacks
    assert trace.span("netexec.run") is trace.NOOP_SPAN
    assert trace.span("fuse.dispatch") is trace.NOOP_SPAN


def test_gc_collection_is_a_host_span():
    t = trace.enable()
    gc.collect()
    trace.disable()
    spans = t.find("host.gc")              # young collections may join
    ev = spans[-1]
    assert ev["ph"] == "X" and ev["dur"] >= 0
    assert ev["args"]["generation"] == 2
    assert ev["args"]["collected"] >= 0
    gc.collect()                           # unhooked: nothing more
    assert len(t.find("host.gc")) == len(spans)


def test_setup_spans_cover_lowering_and_the_store(tmp_path):
    from repro.service import LocalClient, ScheduleStore
    graph = _tiny_graph()
    t = trace.enable()
    served = LocalClient(ScheduleStore(str(tmp_path))).solve(graph, HW)
    lower_network(served.schedule, graph, HW)
    trace.disable()
    counts = t.counts()
    for name in ("service.request", "store.get", "store.put",
                 "lower.network"):
        assert counts.get(name, 0) >= 1, (name, counts)
    (low,) = t.find("lower.network")
    assert low["args"]["graph"] == "scoped"
