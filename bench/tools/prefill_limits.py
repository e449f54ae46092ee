"""Readings that a ``prefill`` cell's ``correct`` limit is set from, in one
process.

    python3 bench/tools/prefill_limits.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out <file.json>]

For every seed: weights and the ring are drawn as a run draws them, the
served executable (``keep="outputs"``) is called on the seed's check
slot, and the largest relative gap of its logits to the reference is
read (the program's reading).  For every control seed two controls are
read the same way, each in the program's place:

* ``program_high``: the program's own path with every product at
  ``Precision.HIGH`` (three bf16 passes) through
  ``fuse.MATMUL_PRECISION``;
* ``reference_high``: the reference in three bf16 passes
  (``prefill_reference.py``, ``precision="high"``).

The limit lies between the largest program reading and the smallest
control reading, as ``limits.py`` sets it for the ``infer`` cells.  Runs
on a TPU only, like ``run.py``; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cell = run.cell_spec(args.workload)
    run.tpu_devices(cell["chips"])
    run.configure_cache()
    import jax
    import numpy as np
    import prefill_reference
    from repro.lower import fuse
    prefill = run.load_module(os.path.join(BENCH, "modes", "prefill.py"))

    prep = prefill.prepare(cell)
    cfg, ring = cell["config"], int(cell["traffic"]["ring"])
    batch, seq = prep["batch"], prep["seq"]

    def readings(seed, control):
        """The program's reading on the seed's check slot and, with
        ``control``, the reference's in three bf16 passes.  Everything
        the seed made is freed on return."""
        weights, slots = prefill.make_arrays(cfg, batch, seq, seed, ring)
        slot = int(np.random.default_rng(seed % 2 ** 64).integers(ring))
        runner = prefill.runners_for(prep["nplan"], weights,
                                     [slots[slot]])[0]
        runner()
        logits = runner().outputs["head"]
        arrays = {**weights, **slots[slot]}
        del weights, slots, runner
        got = {"program": prefill_reference.compare(cfg, arrays, logits,
                                                    batch, seq)}
        if control:
            del logits
            ctrl = prefill_reference.forward(cfg, arrays, batch, seq, "high")
            got["reference_high"] = prefill_reference.compare(
                cfg, arrays, ctrl, batch, seq)
        return got

    out = {"workload": args.workload, "program": {}, "program_high": {},
           "reference_high": {}}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in seeds:
        t = time.perf_counter()
        got = readings(seed, seed in controls)
        for k, v in got.items():
            out[k][seed] = v
        print(json.dumps({"seed": seed, **got,
                          "seconds": time.perf_counter() - t}), flush=True)

    fuse.MATMUL_PRECISION = jax.lax.Precision.HIGH
    fuse.clear_cache()                  # the cache key holds no precision
    for seed in controls:
        t = time.perf_counter()
        out["program_high"][seed] = readings(seed, False)["program"]
        print(json.dumps({"seed": seed,
                          "program_high": out["program_high"][seed],
                          "seconds": time.perf_counter() - t}), flush=True)

    prog = list(out["program"].values())
    ctrl = [v for k in ("program_high", "reference_high")
            for v in out[k].values()]
    out["lower"] = max(prog) if prog else None
    out["upper"] = min(ctrl) if ctrl else None
    print(json.dumps({"lower": out["lower"], "upper": out["upper"]}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
