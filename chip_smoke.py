"""Chip smoke run: the served schedule path on one TPU chip.

    python chip_smoke.py

Drives the paper's inference deployment — ResNet-50 at batch 64 on the
``eyeriss_multinode()`` template, the schedule service's template —
through the entry points a user calls, in one process:

1. device   -- JAX must find a TPU.  Anything else exits non-zero naming
               the platform found; there is no fallback to the CPU.
2. serve    -- requests through ``service.client.LocalClient`` over a
               fresh store: the first resolves ``cold``, the repeat
               ``cached``, and none is ``degraded``.
3. execute  -- the served schedule is lowered (``lower_network``) and run
               as one fused executable (backend ``compiled``); every
               layer is checked against the ``kernels/ref.py`` oracle
               under ``exec.ORACLE_TOL``, then ``keep="boundary"`` runs
               are timed with ``measure_network``.
4. autotune -- ``autotune_network(k=3)``: every candidate executes and
               one is promoted into the store.

Each phase prints its numbers on a line of its own.  The last line of
stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``; a failed phase exits non-zero and prints no such line.
Outputs (the schedule store, ``summary.json``) go to
``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
NET, BATCH, K = "resnet", 64, 3


class PhaseError(RuntimeError):
    """A phase's check failed; the message names the phase."""


def _require(cond: bool, phase: str, msg: str) -> None:
    if not cond:
        raise PhaseError(f"{phase}: {msg}")


def check_device() -> dict:
    """Phase 1: the device JAX found, which must be a TPU."""
    from repro.kernels.backend import device_info
    try:
        dev = device_info()
    except RuntimeError as e:
        raise PhaseError(f"device: JAX found no backend: {e}") from e
    _require(dev["platform"] == "tpu", "device",
             f"JAX found platform {dev['platform']!r} "
             f"({dev['kind']}), not 'tpu'")
    return dev


def phase_serve(graph, hw, store_dir: str):
    """Phase 2: cold then cached through ``LocalClient``; returns the
    served schedule."""
    from repro.service import LocalClient, ScheduleStore
    shutil.rmtree(store_dir, ignore_errors=True)    # the first must be cold
    client = LocalClient(ScheduleStore(store_dir))
    served = [client.solve(graph, hw) for _ in range(3)]
    sources = [r.source for r in served]
    _require(sources == ["cold", "cached", "cached"], "serve",
             f"sources {sources}, want cold then cached")
    _require(not any(r.degraded for r in served), "serve",
             f"degraded answer: {[r.error for r in served]}")
    sched = served[0].schedule
    _require(sched.valid, "serve", "no valid schedule")
    _require(served[1].schedule.total_energy_pj == sched.total_energy_pj,
             "serve", "cached schedule differs from the solved one")
    return sched, {"sources": sources,
                   "seconds": [r.seconds for r in served],
                   "segments": len(sched.chain.segments)}


def phase_execute(sched, graph, hw, iters: int = 5) -> dict:
    """Phase 3: lower, run fused, check every layer, time the serving
    variant."""
    from repro.lower.exec import ORACLE_TOL
    from repro.lower.netexec import (compare_network, make_network_inputs,
                                     measure_network, network_runner)
    from repro.lower.netplan import lower_network

    nplan = lower_network(sched, graph, hw)
    bad = nplan.invalid_layers()
    _require(not bad, "execute", f"invalid plans: {bad}")
    inputs = make_network_inputs(nplan, seed=0)
    ex = network_runner(nplan, inputs, backend="compiled", keep="all")()
    verify_first_s = ex.seconds
    ver = compare_network(nplan, ex, inputs)
    del ex                  # every layer output: gigabytes at batch 64
    _require(ver.ok, "execute",
             f"layer {ver.worst_layer} off the oracle by "
             f"{ver.max_rel_err:.3e} (tolerance {ORACLE_TOL})")
    serve = network_runner(nplan, inputs, backend="compiled",
                           keep="boundary")
    first_s = serve().seconds                       # compiles
    run_s = measure_network(nplan, runner=serve, warmup=1, iters=iters,
                            backend="compiled")
    return {"layers": len(nplan.order), "segments": len(nplan.segments),
            "verify_first_call_seconds": verify_first_s,
            "first_call_seconds": first_s,
            "compile_seconds": first_s - run_s,
            "run_seconds": run_s,
            "worst_layer": ver.worst_layer,
            "max_rel_err": ver.max_rel_err, "tolerance": ORACLE_TOL}


def phase_autotune(graph, hw, store_dir: str, k: int = K) -> dict:
    """Phase 4: every top-k candidate executes, one is promoted."""
    from repro.service import ScheduleStore, autotune_network
    report = autotune_network(graph, hw, store=ScheduleStore(store_dir),
                              k=k)
    _require(not report["skipped"], "autotune",
             f"skipped candidates: {report['skipped']}")
    _require(report["n_candidates"] == k
             and report["n_executed"] == report["n_candidates"],
             "autotune", f"{report['n_executed']} of "
             f"{report['n_candidates']} candidates executed, want {k}")
    _require(report.get("promoted") is True, "autotune",
             f"nothing promoted: {report.get('promote_error')}")
    return {key: report.get(key) for key in (
        "n_candidates", "n_executed", "promoted_rank",
        "promoted_measured_seconds", "argmin_measured_seconds",
        "rank_agreement", "autotune_seconds", "device")}


def run_phases(net: str, batch: int, out_dir: str, k: int = K) -> dict:
    """Phases 2-4 on one (net, batch); prints a line per phase."""
    from repro.hw.presets import eyeriss_multinode
    from repro.workloads.nets import get_net

    graph, hw = get_net(net, batch=batch), eyeriss_multinode()
    store_dir = os.path.join(out_dir, "store")
    results = {"net": net, "batch": batch, "hw": hw.name}
    t0 = time.perf_counter()
    sched, results["serve"] = phase_serve(graph, hw, store_dir)
    print("serve:", json.dumps(results["serve"]), flush=True)
    results["execute"] = phase_execute(sched, graph, hw)
    print("execute:", json.dumps(results["execute"]), flush=True)
    results["autotune"] = phase_autotune(graph, hw, store_dir, k)
    print("autotune:", json.dumps(results["autotune"]), flush=True)
    results["wall_seconds"] = time.perf_counter() - t0
    return results


def main() -> int:
    try:
        dev = check_device()
    except PhaseError as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print("device:", json.dumps(dev), flush=True)
    try:
        from repro.kernels.backend import configure_compile_cache
        print("compile cache:", configure_compile_cache(), flush=True)
        os.makedirs(OUT_DIR, exist_ok=True)
        results = run_phases(NET, BATCH, OUT_DIR)
    except PhaseError as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAIL (exception above)", file=sys.stderr)
        return 1
    results["device"] = dev
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
