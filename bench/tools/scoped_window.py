"""Run one cell's traced window with the program's own instrumentation on,
and print its per-layer readings.

    python3 bench/tools/scoped_window.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

Set-up is the cell's own (``modes/infer.py``: ``prepare``,
``make_arrays``, ``runners_for``, every ring slot called once), with the
program's tracer enabled and mirrored into the profiler
(``obs.trace.Tracer(profiler=True)``), and ``FusedNetwork.op_layers``
asked once the executable is warm.  The window calls the slots in turn,
as the cell's traced run does, for ``seconds`` (at most the traffic's
``trace_seconds``) under ``jax.profiler``.  The trace is reduced by
``trace_layers.reduce_scoped`` and read by the per-layer metric readers
of ``metrics/``.  The last line of standard output is JSON: the metrics,
``layer_time``, ``clock_skew_ms``, ``program_gaps``, ``setup_spans``,
the seconds ``op_layers`` took, and ``calls_ms``.  With ``--out`` the
trace is kept there.  Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "modes"))

import run  # noqa: E402

READERS = ("mfu.infer", "device_idle.infer", "conv_roofline.infer",
           "pool_roofline.infer", "idle_dispatch.infer", "idle_wait.infer")


def layer_work(layers) -> dict:
    import work
    return {l["name"]: {"kind": l["kind"], "flops": work.flops(l),
                        "min_bytes": work.min_bytes(l)} for l in layers}


def measure(cell: dict, seed: int, seconds: float, peaks: dict,
            out_dir: str = None) -> dict:
    import jax
    import numpy as np

    import infer
    import trace_layers
    from repro.lower.fuse import fused_runner
    from repro.obs import trace

    tracer = trace.enable(trace.Tracer(profiler=True))
    try:
        prep = infer.prepare(cell)
        traffic = cell["traffic"]
        weights, slots = infer.make_arrays(prep["layers"], seed,
                                           int(traffic["ring"]))
        runners = infer.runners_for(prep["nplan"], weights, slots)
        for r in runners:
            r()
        t = time.perf_counter()
        op_layers = fused_runner(prep["nplan"]).op_layers("boundary")
        op_layers_s = time.perf_counter() - t
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = out_dir or tempfile.mkdtemp(prefix="scoped_trace_")
        setup_end = time.perf_counter()
        jax.profiler.start_trace(trace_dir)
        calls, ends = 0, []
        with jax.profiler.TraceAnnotation("bench.window"):
            start = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.next_input"):
                    slot = calls % len(runners)
                with jax.profiler.TraceAnnotation("bench.forward"):
                    runners[slot]()
                calls += 1
                ends.append(time.perf_counter())
                if ends[-1] - start >= seconds:
                    break
        jax.profiler.stop_trace()
    finally:
        trace.disable()
    kinds = {l["name"]: l["kind"] for l in prep["layers"]}
    reduced = trace_layers.reduce_scoped(trace_dir, op_layers, kinds)
    if out_dir is None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"batch": prep["batch"], "forwards": calls,
           "window_s": ends[-1] - start,
           "macs_per_forward": prep["macs"], "trace": reduced,
           "layer_work": layer_work(prep["layers"]), "peaks": peaks}
    metrics = {}
    for name in READERS:
        value = run.load_module(os.path.join(BENCH, "metrics",
                                             name + ".py")).read(ctx)
        if value is not None:
            metrics[name] = value
    reduced = reduced or {}
    ms = np.diff(np.asarray([start] + ends)) * 1e3
    return {
        "workload": cell["name"], "seed": seed, "forwards": calls,
        "images_per_s": calls * prep["batch"] / ctx["window_s"],
        "metrics": metrics,
        "busy_s": reduced.get("busy_s"), "window_s": reduced.get("window_s"),
        "layer_time": reduced.get("layer_time"),
        "clock_skew_ms": reduced.get("clock_skew_ms"),
        "idle_by_span": reduced.get("idle_by_span"),
        "idle_by_span_late": reduced.get("idle_by_span_late"),
        "program_gaps": reduced.get("program_gaps"),
        "idle_gaps": reduced.get("idle_gaps"),
        "setup_spans": trace_layers.setup_spans(
            tracer.events, threading.get_ident(),
            setup_end - tracer.epoch),
        "op_layers": len(op_layers), "op_layers_s": op_layers_s,
        "calls_ms": {"p50": float(np.median(ms)), "max": float(ms.max())},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = run.cell_spec(args.workload)
    devices = run.tpu_devices(cell["chips"])
    peaks = run.peaks_of(devices[0].device_kind)
    run.configure_cache()
    print(json.dumps(measure(cell, args.seed, args.seconds, peaks,
                             args.out)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
