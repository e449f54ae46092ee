"""``trace_layers``: the clock skew, device time per layer and idle by
program span on made-up events with a known skew; the four readers on a
made-up ``ctx``; a scoped trace recorded on a TPU v5 lite chip
(``tools/record_scoped_trace.py``, kept as ``data/scoped.xplane.pb``);
and ``tools/scoped_window.py`` on the CPU."""
from __future__ import annotations

import json
import os
import time

import jax
import pytest

import run
import tiny_arch
import trace_layers

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SKEW = 30          # the device clock runs 30 ns behind the host's

# two calls: feed, dispatch, wait on the host; on the device one module
# a call, stamped SKEW early.  On the host's clock the first module
# starts 30 ns after its dispatch, the second as its dispatch starts.
SPANS = [("netexec.run", 100, 400, "main"), ("fuse.feed", 100, 120, "main"),
         ("fuse.dispatch", 120, 200, "main"),
         ("netexec.wait", 200, 400, "main"),
         ("netexec.run", 500, 800, "main"), ("fuse.feed", 500, 530, "main"),
         ("fuse.dispatch", 530, 600, "main"),
         ("netexec.wait", 600, 800, "main"),
         ("host.gc", 420, 480, "main"), ("fuse.dispatch", 0, 50, "other")]
MODULES = {"/device:TPU:0": [("jit_fn(1)", 150 - SKEW, 350 - SKEW),
                             ("jit_fn(1)", 530 - SKEW, 790 - SKEW)]}
OPS = {"/device:TPU:0": [
    ("%slice.1 = f32[4] slice(f32[8] %p)", 150 - SKEW, 250 - SKEW),
    ("%fusion.2 = f32[4] fusion(f32[4] %slice.1)", 250 - SKEW, 350 - SKEW),
    ("%copy-start = (f32[4]) copy-start(f32[4] %p)", 530 - SKEW,
     540 - SKEW),
    ("%slice.1 = f32[4] slice(f32[8] %p)", 540 - SKEW, 660 - SKEW),
    ("%fusion.2 = f32[4] fusion(f32[4] %slice.1)", 660 - SKEW, 790 - SKEW)]}
OP_LAYERS = {"slice.1": "conv1", "fusion.2": "pool1"}


def test_instruction_names():
    assert trace_layers.instruction(
        "%slice.567 = f32[64,64,56,56] slice(f32[64,64,113,113] %pad.4)"
    ) == "slice.567"
    assert trace_layers.instruction("%copy-start = (f32[8]) copy-start()"
                                    ) == "copy-start"


def test_skew_pairs_modules_with_dispatch_and_wait():
    main = [sp for sp in SPANS if sp[3] == "main"]
    skew, slack, pairs = trace_layers.clock_skew(
        MODULES, [(s, e) for n, s, e, _ in main if n == "fuse.dispatch"],
        [(s, e) for n, s, e, _ in main if n == "netexec.wait"])
    assert skew == pytest.approx(SKEW)       # the second call pins it
    assert pairs == 2
    # shifted, the modules end at 350 and 790, their waits at 400, 800
    assert slack == pytest.approx(10)


def test_skew_left_out_when_counts_differ():
    assert trace_layers.clock_skew(MODULES, [(120, 200)], [(200, 400)]) \
        is None
    assert trace_layers.clock_skew(MODULES, [], []) is None


def test_layer_time_sums_clipped_ops_by_layer():
    layers, other = trace_layers.layer_time(OPS, OP_LAYERS, 0, 1000)
    assert layers == {"conv1": 100 + 120, "pool1": 100 + 130}
    assert other == {"copy-start": 10}
    # a window that cuts the second call, on the device's own stamps
    layers, _ = trace_layers.layer_time(OPS, OP_LAYERS, 0, 600)
    assert layers == {"conv1": 100 + (600 - (540 - SKEW)), "pool1": 100}


def test_idle_by_span_after_the_shift():
    main = [sp for sp in SPANS if sp[3] == "main"]
    # shifted, the device runs [150,350) and [530,790); the window's
    # idle is [100,150) [350,530) [790,800)
    idle = trace_layers.idle_by_span(OPS, main, SKEW, 100, 800)
    # feed + dispatch, [100,200) and [500,600): 50 + 30
    assert idle["dispatch"] == pytest.approx(50 + 30)
    # wait, [200,400) and [600,800): 50 + 10
    assert idle["wait"] == pytest.approx(50 + 10)


def test_program_gaps_named_by_innermost_span():
    spans = [("bench.forward", 100, 800)] + [sp[:3] for sp in SPANS]
    gaps = trace_layers.program_gaps(OPS, spans, SKEW, 100, 800)
    assert gaps == [["host.gc", 180], ["fuse.dispatch", 50],
                    ["netexec.wait", 10]]


def test_setup_spans_top_level_and_children():
    ev = [{"name": "service.request", "ph": "X", "ts": 0.0, "dur": 5.0,
           "tid": 1},
          {"name": "store.get", "ph": "X", "ts": 0.5, "dur": 0.1, "tid": 1},
          {"name": "solve.dp", "ph": "X", "ts": 1.0, "dur": 3.0, "tid": 1},
          {"name": "dp.select", "ph": "X", "ts": 1.5, "dur": 1.0, "tid": 1},
          {"name": "store.put", "ph": "X", "ts": 4.5, "dur": 0.2, "tid": 1},
          {"name": "lower.network", "ph": "X", "ts": 6.0, "dur": 1.0,
           "tid": 1},
          {"name": "solve.segment", "ph": "X", "ts": 1.0, "dur": 2.0,
           "tid": 2},
          {"name": "netexec.run", "ph": "X", "ts": 9.0, "dur": 1.0,
           "tid": 1}]
    assert trace_layers.setup_spans(ev, 1, until=8.0) == {
        "service.request": 5.0, "store.get": 0.1, "solve.dp": 3.0,
        "store.put": 0.2, "lower.network": 1.0}


def _reader(name):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py")).read


def test_readers_on_a_made_up_ctx():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    work = {"c1": {"kind": "conv", "flops": 200, "min_bytes": 10},
            "c2": {"kind": "conv", "flops": 100, "min_bytes": 40},
            "p1": {"kind": "pool", "flops": 9, "min_bytes": 50},
            "f": {"kind": "fc", "flops": 10, "min_bytes": 1}}
    ctx = {"forwards": 3, "peaks": peaks, "layer_work": work,
           "trace": {"window_s": 100.0,
                     "layers": {"c1": 10.0, "c2": 20.0, "p1": 60.0},
                     "idle_by_span": {"dispatch": 7.0, "wait": 2.5}}}
    # conv: max(2, 1) + max(1, 4) = 6 s least a forward, 3 forwards, 30 s
    assert _reader("conv_roofline.infer")(ctx) == pytest.approx(60.0)
    # pool: max(0.09, 5) = 5 s, 3 forwards, 60 s
    assert _reader("pool_roofline.infer")(ctx) == pytest.approx(25.0)
    assert _reader("idle_dispatch.infer")(ctx) == pytest.approx(7.0)
    assert _reader("idle_wait.infer")(ctx) == pytest.approx(2.5)


def test_readers_find_nothing_without_the_program_keys():
    """The parent program has no scopes and no mirrored spans: the
    readers then give nothing and raise nothing."""
    ctx = {"forwards": 3, "peaks": {}, "trace": {
        "window_s": 1.0, "busy_s": 0.5}}
    for name in ("conv_roofline.infer", "pool_roofline.infer",
                 "idle_dispatch.infer", "idle_wait.infer"):
        assert _reader(name)(ctx) is None
        assert _reader(name)({}) is None


def test_recorded_scoped_trace():
    """Five calls of the tiny net's fused executable with the program's
    tracer mirrored, recorded on one TPU v5 lite chip."""
    with open(os.path.join(DATA, "scoped.op_layers.json")) as f:
        rec = json.load(f)
    path = os.path.join(DATA, "scoped.xplane.pb")
    _, modules, _, program = trace_layers.read_trace(path)
    names = [n for n, *_ in program]
    for span in ("netexec.run", "fuse.feed", "fuse.dispatch",
                 "netexec.wait"):
        assert names.count(span) == rec["calls"], span
    r = trace_layers.reduce_scoped(path, rec["op_layers"], rec["kinds"])
    assert r is not None and r["devices"] == 1
    assert r["clock_skew_ms"]["pairs"] == rec["calls"]
    assert r["clock_skew_ms"]["slack"] >= 0
    lt = r["layer_time"]
    assert "unattributed" in lt["by_kind"]
    assert set(r["layers"]) <= set(rec["kinds"])
    assert sum(lt["by_kind"].values()) == pytest.approx(
        sum(r["layers"].values()) + lt["by_kind"]["unattributed"])
    for key in ("idle_by_span", "idle_by_span_late"):
        idle = r[key]
        assert idle["dispatch"] + idle["wait"] <= \
            r["window_s"] - r["busy_s"] + 1e-6


def test_scoped_window_runs_on_the_cpu(monkeypatch):
    """The tool end to end on the tiny net: set-up spans and the instruction
    map are read; the CPU's trace holds no TPU plane, so no device metric."""
    import importlib.util
    from repro.lower import fuse
    from repro.workloads import nets
    monkeypatch.setitem(nets.NETS, "bench_tiny",
                        lambda batch=4: tiny_arch.program_graph(batch))
    fuse.clear_cache()
    spec = importlib.util.spec_from_file_location(
        "scoped_window", os.path.join(run.BENCH, "tools", "scoped_window.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell = {"name": "tiny.infer_b4", "chips": 1, "config": tiny_arch.CONFIG,
            "arch": tiny_arch,
            "traffic": {"mode": "infer", "batch": 4,
                        "template": "eyeriss_multinode", "ring": 2}}
    t = time.perf_counter()
    line = tool.measure(cell, 7, 0.2, {})
    assert time.perf_counter() - t < 120
    fuse.clear_cache()
    assert line["forwards"] >= 1 and line["metrics"] == {}
    assert line["op_layers"] > 0
    for name in ("service.request", "store.get", "store.put",
                 "lower.network", "netexec.run"):
        assert line["setup_spans"].get(name, 0) > 0, name
    assert jax.devices()[0].platform == "cpu"
