"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root.  Everything that belongs to one configuration, traffic mix or
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json``: the configuration's sizes, and beside
  it the layer list that the plain reference runs (its ``arch`` file);
* ``bench/traffic/<traffic>.json``: the traffic mix; its ``mode`` names
  the code ``bench/modes/<mode>.py`` that runs it;
* ``bench/checks/<cell>.json``: the limit of each number that decides
  ``correct``;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/peaks.json``: the chip's peaks, by ``device_kind``.

A run needs a TPU: on any other platform, or with fewer chips than the
cell asks for, it exits 1 and prints no result.  With ``--trace 0`` the
last line of standard output holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line holds the
per-layer metrics, the device's busy time, a breakdown and the device
time by class of operation.  Every line also gives the milliseconds a
call of the window took (``calls_ms``: 5th, 50th, 95th percentile and
the longest).  The numbers that decide ``correct`` come last in the
line, each beside its limit, and again as the last lines of standard
error.  Compiled programs are kept in ``<checkout>/.jax_cache``,
so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(name: str) -> Dict:
    """Everything one cell runs with, loaded from its files by name."""
    bm = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": cfg,
        "arch": load_module(os.path.join(os.path.dirname(
            os.path.join(ROOT, conf["file"])), cfg["arch"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")),
        "checks": load_json(os.path.join(BENCH, "checks", name + ".json")),
        "end_to_end": [m for m in bm["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bm["per_layer"] if _applies(m, name)],
    }


def tpu_devices(chips: int) -> List:
    """The chips the cell runs on; anything but enough TPUs exits 1."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {devs[0].platform!r}"
                         f" ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peaks_of(kind: str) -> Dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def configure_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    a fixed path, whatever the environment set: the program takes the
    directory the benchmark gives it, so no two checkouts share one."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.kernels.backend import configure_compile_cache
    path = configure_compile_cache()
    # every program this run uses, small ones included, lands in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             devices: List, t0: float, peaks: Dict) -> Dict:
    """Run the cell's mode and assemble the result line (a dict, with
    ``checks`` last)."""
    import jax
    mode = load_module(os.path.join(BENCH, "modes",
                                    cell["traffic"]["mode"] + ".py"))
    res = mode.run(cell, seed, seconds, trace, devices, t0)
    metrics = {}
    if trace:
        ctx = {**res["ctx"], "peaks": peaks}
        for m in cell["per_layer"]:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    all_devs = jax.devices()
    device = {"platform": all_devs[0].platform,
              "kind": all_devs[0].device_kind, "count": len(all_devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        tr = res["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        line["op_classes"] = tr["op_classes"]
    line["calls_ms"] = res["calls_ms"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res["checks"].items()}
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cell_spec(args.workload)
    devices = tpu_devices(cell["chips"])
    peaks = peaks_of(devices[0].device_kind)
    configure_cache()

    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices, T0, peaks)
    print(json.dumps(line), flush=True)
    print(f"calls_ms {json.dumps(line['calls_ms'])}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
