"""Closed-loop inference: one client forwards image batches back to back.

Set-up follows the served path: the configuration's net is built by the
program's builder and checked against the configuration's own layer
list, the schedule is solved through ``service.client.LocalClient`` over
a fresh store, lowered (``lower_network``) and run as one fused
executable (``netexec.network_runner(backend="compiled",
keep="boundary")``).  Weights and a ring of input batches are drawn on
the device from the seed in one jitted call.  Every ring slot is called
once in set-up, so that nothing compiles in the window.

The window calls the slots in turn until ``seconds`` have passed (in a
traced run, at most the traffic's ``trace_seconds``); each call ends in
``block_until_ready``.  The outputs of the last call on one
slot, drawn from the seed, are kept.  After the window the peak memory
is read, the program's state is freed, and the plain reference
(``reference.py``) runs over that slot's inputs: every boundary output
of that call is compared against it.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

import reference
import trace_reduce
import work

#: trace events the JAX runtime records while it traces, lowers or
#: compiles; any of them inside the window fails the run
_COMPILE_EVENT_PREFIX = "/jax/core/compile/"


def count_compiles() -> List[int]:
    """A one-element list that counts JAX's compile events from now on."""
    count = [0]

    def on_event(event: str, *args, **kwargs) -> None:
        if event.startswith(_COMPILE_EVENT_PREFIX):
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one past 32 bits."""
    s = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0]) & 0x7FFFFFFF),
                              int(s[1]) & 0x7FFFFFFF)


def make_arrays(layers: List[Mapping], seed: int, ring: int):
    """(weights, [inputs of each ring slot]), drawn on the device in one
    jitted call from the seed."""
    spec = reference.feeds(layers)
    wnames = sorted(k for k in spec if k.endswith(".W"))
    inames = sorted(k for k in spec if k.endswith(".I"))

    def gen(key):
        weights = {}
        for i, k in enumerate(wnames):
            shape, scale = spec[k]
            weights[k] = jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32) * scale
        slots = []
        for r in range(ring):
            slot_key = jax.random.fold_in(key, len(wnames) + r)
            slots.append({k: jax.random.normal(
                jax.random.fold_in(slot_key, j), spec[k][0], jnp.float32)
                for j, k in enumerate(inames)})
        return weights, slots

    return jax.block_until_ready(jax.jit(gen)(seed_key(seed)))


def check_graph(graph, layers: List[Mapping], cfg: Mapping,
                batch: int) -> int:
    """The program's graph must be the configuration's layer list, layer
    by layer, and count the conv+fc MACs the configuration states."""
    keys = ("name", "kind", "N", "C", "K", "X", "Y", "R", "S", "stride",
            "src")
    prog = [work.from_spec(l) for l in graph.layers]
    if len(prog) != len(layers):
        raise RuntimeError(f"program graph has {len(prog)} layers, the "
                           f"configuration {len(layers)}")
    for mine, theirs in zip(layers, prog):
        diff = {k: (mine.get(k), theirs.get(k)) for k in keys
                if mine.get(k) != theirs.get(k)}
        if diff:
            raise RuntimeError(f"layer {mine['name']}: configuration and "
                               f"program differ in {diff}")
    stated = cfg["conv_fc_macs"].get(str(batch))
    counted = work.total_macs(prog)
    if stated is None or counted != stated:
        raise RuntimeError(f"conv+fc MACs at batch {batch}: counted "
                           f"{counted}, configuration states {stated}")
    return counted


def prepare(cell: Dict) -> Dict:
    """Build, check, solve and lower the cell's net: the set-up before
    any array exists."""
    from repro.hw.presets import PRESETS
    from repro.lower.netplan import lower_network
    from repro.service import LocalClient, ScheduleStore
    from repro.workloads.nets import get_net

    cfg, traffic = cell["config"], cell["traffic"]
    batch = int(traffic["batch"])
    layers = cell["arch"].layers(cfg, batch)
    graph = get_net(cfg["builder"], batch=batch)
    macs = check_graph(graph, layers, cfg, batch)
    hw = PRESETS[traffic["template"]]()

    store_dir = tempfile.mkdtemp(prefix="bench_store_")
    try:
        served = LocalClient(ScheduleStore(store_dir)).solve(graph, hw)
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
    return {"batch": batch, "layers": layers, "macs": macs,
            "nplan": nplan, "lower_s": lower_s}


def call_quantiles(start: float, ends: List[float]) -> Dict[str, float]:
    """Milliseconds per call of the window: 5th, 50th and 95th
    percentile and the longest, to tell a uniformly slower run from one
    with stalls."""
    ms = np.diff(np.asarray([start] + ends)) * 1e3
    p5, p50, p95 = np.percentile(ms, [5, 50, 95])
    return {"p5": float(p5), "p50": float(p50), "p95": float(p95),
            "max": float(ms.max())}


def runners_for(nplan, weights: Mapping, slots: List[Mapping]) -> List:
    """One serving runner per ring slot, all on one fused executable."""
    from repro.lower.netexec import network_runner
    return [network_runner(nplan, {**weights, **acts}, backend="compiled",
                           keep="boundary") for acts in slots]


def run(cell: Dict, seed: int, seconds: float, trace: bool,
        devices: List, t0: float) -> Dict:
    from repro.lower.fuse import fused_runner

    compile_events = count_compiles()
    prep = prepare(cell)
    batch, layers, macs = prep["batch"], prep["layers"], prep["macs"]
    nplan, lower_s = prep["nplan"], prep["lower_s"]
    traffic = cell["traffic"]

    weights, slots = make_arrays(layers, seed, int(traffic["ring"]))
    runners = runners_for(nplan, weights, slots)
    fused = fused_runner(nplan)
    first_s = runners[0]().seconds               # compiles, or loads
    steady_s = runners[1 % len(runners)]().seconds
    for r in runners[2:]:
        r()
    check_slot = int(np.random.default_rng(int(seed) % 2 ** 64)
                     .integers(len(runners)))

    # a traced run reads every device event of its window back on the
    # host; a traffic with many short calls caps that window
    if trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
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
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce_file(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise RuntimeError("the trace holds no device operation")
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
    del slots

    errs = reference.compare(layers, checked, kept)
    sinks = [l["name"] for l in layers
             if not any(l["name"] in m["src"] for m in layers)]
    missing = [s for s in sinks if s not in kept]
    worst = max(errs.values()) if errs else float("inf")
    limit = cell["checks"]["max_rel_err"]["limit"]
    correct = not missing and bool(np.isfinite(worst)) and worst <= limit

    return {
        "correct": correct,
        "attempted": calls,
        "failed": 0,
        "e2e": {"images_per_s": calls * batch / window_s,
                "setup_s": setup_s},
        "ctx": {"batch": batch, "forwards": calls, "window_s": window_s,
                "macs_per_forward": macs, "lower_s": lower_s,
                "compile_s": first_s - steady_s, "trace": reduced},
        "memory_peak_bytes": peak,
        "trace": reduced,
        "calls_ms": call_quantiles(start, ends),
        "checks": {"max_rel_err": (worst, limit),
                   "missing_outputs": (len(missing), 0)},
    }
