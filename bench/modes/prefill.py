"""Closed-loop prefill: one client forwards token sequences back to back.

Set-up follows the served path, as ``infer.py`` does: the configuration's
model is built by the program's builder from the configuration's sizes
and checked against the configuration's own layer list, loop ties
included; the schedule is solved through ``service.client.LocalClient``
over a fresh store (timed: ``solve_s``), lowered (``lower_network``) and
run as one fused executable that returns the graph's outputs alone
(``netexec.network_runner(backend="compiled", keep="outputs")``: the last
positions' logits).  The weights, once for every loop step, and a ring
of embedded sequences are drawn on the device from the seed in one
jitted call.  Every ring slot is called once in set-up, so that nothing
compiles in the window.

The window calls the slots in turn until ``seconds`` have passed; each
call ends in ``block_until_ready``.  The logits of the last call on one
slot, drawn from the seed, are kept.  A traced run also maps the
executable's instructions to layers (``FusedNetwork.compiled_text``) and
reduces the trace by layer (``trace_layers.reduce_scoped``).  After the
window the peak memory is read, the program's state is freed, and the
plain reference (``prefill_reference.py``) runs over that slot's inputs:
the logits are compared against it.

A traced run prints three lines before the result line: ``kind_time``
(device seconds of the window by layer kind), ``step_time`` (by loop
step, from the ``s{t}.`` prefix; the head and the input under
``other``) and ``fused_away`` (the attention, norm and glu layers that no
device op carries the scope of).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import infer  # noqa: E402
import prefill_reference  # noqa: E402
import prefill_work  # noqa: E402
import trace_layers  # noqa: E402

#: the layer kinds whose device time and fused-away layers a traced run
#: reports
NEW_KINDS = ("attention", "norm", "glu")


def builder_sizes(cfg: Mapping, seq: int) -> Dict:
    """The ``looplm`` builder's sizes from a configuration's keys."""
    return {"seq": seq, "hidden": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "ffn": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"],
            "steps": cfg["total_ut_steps"], "vocab": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"])}


def check_graph(graph, layers: List[Mapping], cfg: Mapping, batch: int,
                seq: int) -> int:
    """The program's graph must be the configuration's layer list, layer
    by layer and tie by tie, and count the MACs the configuration
    states."""
    prog = [prefill_work.from_spec(l) for l in graph.layers]
    if len(prog) != len(layers):
        raise RuntimeError(f"program graph has {len(prog)} layers, the "
                           f"configuration {len(layers)}")
    for mine, theirs in zip(layers, prog):
        diff = {k: (mine.get(k), theirs.get(k))
                for k in set(mine) | set(theirs)
                if mine.get(k) != theirs.get(k)}
        if diff:
            raise RuntimeError(f"layer {mine['name']}: configuration and "
                               f"program differ in {diff}")
    stated = cfg["macs"]
    counted = prefill_work.total_macs(prog)
    if (stated["batch"], stated["seq"]) != (batch, seq) \
            or counted != stated["total"]:
        raise RuntimeError(f"MACs at batch {batch}, seq {seq}: counted "
                           f"{counted}, configuration states {stated}")
    return counted


def prepare(cell: Dict) -> Dict:
    """Build, check, solve and lower the cell's model: the set-up before
    any array exists."""
    from repro.hw.presets import PRESETS
    from repro.lower.netplan import lower_network
    from repro.service import LocalClient, ScheduleStore
    from repro.workloads.nets import get_net

    cfg, traffic = cell["config"], cell["traffic"]
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    layers = cell["arch"].layers(cfg, batch, seq)
    graph = get_net(cfg["builder"], batch=batch, **builder_sizes(cfg, seq))
    macs = check_graph(graph, layers, cfg, batch, seq)
    hw = PRESETS[traffic["template"]]()

    store_dir = tempfile.mkdtemp(prefix="bench_store_")
    try:
        t = time.perf_counter()
        served = LocalClient(ScheduleStore(store_dir)).solve(graph, hw)
        solve_s = time.perf_counter() - t
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    if served.degraded or not served.schedule.valid:
        raise RuntimeError(f"no valid schedule: {served.error}")
    t = time.perf_counter()
    nplan = lower_network(served.schedule, graph, hw)
    lower_s = time.perf_counter() - t
    bad = nplan.invalid_layers()
    if bad:
        raise RuntimeError(f"invalid plans: {bad}")
    return {"batch": batch, "seq": seq, "layers": layers, "macs": macs,
            "nplan": nplan, "solve_s": solve_s, "lower_s": lower_s}


def make_arrays(cfg: Mapping, batch: int, seq: int, seed: int, ring: int):
    """(weights, [inputs of each ring slot]), drawn on the device in one
    jitted call from the seed (``prefill_reference.feeds``)."""
    spec = prefill_reference.feeds(cfg, batch, seq)
    wnames = sorted(k for k in spec if k.endswith(".W"))
    inames = sorted(k for k in spec if k.endswith(".I"))

    def gen(key):
        weights = {}
        for i, k in enumerate(wnames):
            shape, draw = spec[k]
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            weights[k] = 1.0 + 0.1 * z if draw == "gain" \
                else z * shape[0] ** -0.5
        slots = []
        for r in range(ring):
            slot_key = jax.random.fold_in(key, len(wnames) + r)
            slots.append({k: jax.random.normal(
                jax.random.fold_in(slot_key, j), spec[k][0], jnp.float32)
                for j, k in enumerate(inames)})
        return weights, slots

    return jax.block_until_ready(jax.jit(gen)(infer.seed_key(seed)))


def runners_for(nplan, weights: Mapping, slots: List[Mapping]) -> List:
    """One serving runner per ring slot, all on one fused executable that
    returns the logits alone."""
    from repro.lower.netexec import network_runner
    return [network_runner(nplan, {**weights, **acts}, backend="compiled",
                           keep="outputs") for acts in slots]


def layer_work(layers: List[Mapping]) -> Dict:
    """{attention layer: its kind, flops and least bytes}
    (``prefill_work``)."""
    return {l["name"]: {"kind": l["kind"], "flops": prefill_work.flops(l),
                        "min_bytes": prefill_work.min_bytes(l)}
            for l in layers if l["kind"] == "attention"}


def op_seconds(trace_dir: str) -> Dict[str, float]:
    """{instruction: device seconds in the window} of a trace."""
    ops, _, bench, _ = trace_layers.read_trace(trace_dir)
    ops = {k: v for k, v in ops.items() if v}
    if not ops:
        return {}
    lo, hi = trace_layers.window(ops, bench)
    _, per_op = trace_layers.layer_time(ops, {}, lo, hi)
    return {k: v / 1e9 for k, v in per_op.items()}


def trace_lines(reduced: Mapping, kinds: Mapping[str, str]) -> Dict:
    """``kind_time``, ``step_time`` and ``fused_away`` of a scoped trace."""
    seconds = reduced.get("layers") or {}
    steps: Dict[str, float] = {}
    for name, s in seconds.items():
        key = name.split(".", 1)[0] if name.startswith("s") else "other"
        steps[key] = steps.get(key, 0.0) + s
    return {
        "kind_time": reduced["layer_time"]["by_kind"],
        "step_time": dict(sorted(steps.items())),
        "fused_away": {k: sorted(n for n, kind in kinds.items()
                                 if kind == k and not seconds.get(n))
                       for k in NEW_KINDS}}


def run(cell: Dict, seed: int, seconds: float, trace: bool,
        devices: List, t0: float) -> Dict:
    from repro.lower.fuse import fused_runner, hlo_op_layers

    compile_events = infer.count_compiles()
    prep = prepare(cell)
    batch, seq, layers = prep["batch"], prep["seq"], prep["layers"]
    nplan, traffic, cfg = prep["nplan"], cell["traffic"], cell["config"]

    weights, slots = make_arrays(cfg, batch, seq, seed, int(traffic["ring"]))
    runners = runners_for(nplan, weights, slots)
    fused = fused_runner(nplan)
    first_s = runners[0]().seconds               # compiles, or loads
    steady_s = runners[1 % len(runners)]().seconds
    for r in runners[2:]:
        r()
    check_slot = int(np.random.default_rng(int(seed) % 2 ** 64)
                     .integers(len(runners)))
    kinds = {l["name"]: l["kind"] for l in layers}
    op_layers = op_bytes = None
    if trace:
        text = fused.compiled_text("outputs")
        op_layers = hlo_op_layers(text, nplan.order)
        op_bytes = prefill_work.op_bytes(text)
        del text

    traces, compiles = fused.traces, compile_events[0]
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t0
    kept, calls, ends = None, 0, []
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.next_input"):
                slot = calls % len(runners)
            with jax.profiler.TraceAnnotation("bench.forward"):
                outputs = runners[slot]().outputs
            calls += 1
            if slot == check_slot:
                kept = outputs
            del outputs
            end = time.perf_counter()
            ends.append(end)
            if end - start >= seconds and kept is not None:
                break
    window_s = end - start
    reduced, per_op = None, {}
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_layers.reduce_scoped(trace_dir, op_layers, kinds)
        per_op = op_seconds(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise RuntimeError("the trace holds no device operation")
        for key, value in trace_lines(reduced, kinds).items():
            print(f"{key} {json.dumps(value)}", flush=True)
    if fused.traces != traces or compile_events[0] != compiles:
        raise RuntimeError(
            f"compiled inside the window: {fused.traces - traces} traces, "
            f"{compile_events[0] - compiles} compile events")

    # the CPU backend keeps no memory statistics: 0 there
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"memory_peak_bytes {peak}", flush=True)
    del runners, fused, nplan
    checked = {**weights, **slots[check_slot]}
    del slots, weights

    missing = [] if "head" in kept else ["head"]
    worst = float("inf") if missing else prefill_reference.compare(
        cfg, checked, kept["head"], batch, seq)
    limit = cell["checks"]["max_rel_err"]["limit"]
    correct = not missing and bool(np.isfinite(worst)) and worst <= limit

    return {
        "correct": correct,
        "attempted": calls,
        "failed": 0,
        "e2e": {"images_per_s": calls * batch / window_s,
                "setup_s": setup_s},
        "ctx": {"batch": batch, "forwards": calls, "window_s": window_s,
                "macs_per_forward": prep["macs"], "solve_s": prep["solve_s"],
                "lower_s": prep["lower_s"], "compile_s": first_s - steady_s,
                "trace": reduced, "kinds": kinds,
                "layer_work": layer_work(layers),
                "op_layers": op_layers, "op_bytes": op_bytes,
                "op_seconds": per_op},
        "memory_peak_bytes": peak,
        "trace": reduced,
        "calls_ms": infer.call_quantiles(start, ends),
        "checks": {"max_rel_err": (worst, limit),
                   "missing_outputs": (len(missing), 0)},
    }
