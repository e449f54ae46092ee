"""Solver-speed benchmark: batched cost model vs scalar judge, batched
inter-layer level vs the scalar PR-1 baseline, and end-to-end solve times,
emitted as a JSON perf record (``BENCH_solver.json`` at the repo root) to
track the repo's bench trajectory.  ``--calibrate``/``--network`` add the
lowering sweeps (per-kernel and whole-network), written to
``BENCH_calibration.json`` / ``BENCH_network.json``; ``--service`` adds
the schedule-service sweep (cold vs warm vs cached solve latency through
the store, plus measured top-k autotuning), written to
``BENCH_service.json``; ``--chaos`` adds the resilience sweep (request
availability + latency percentiles through the SolveServer under a
seeded ~20% store-fault + slow-solve schedule), written to
``BENCH_robustness.json``; ``--obs`` adds the observability sweep
(instrumentation overhead off/metrics/tracing on the resnet/b64 cold
solve, plus a traced multi-node chaos run), written to
``BENCH_obs.json`` with the Chrome trace at ``TRACE_obs.json``.

    python benchmarks/bench_solver_speed.py [--quick] [--out perf.json]

Record shape:
    {
      "cost_model": {"schemes_scored": N, "scalar_schemes_per_sec": ...,
                     "batched_schemes_per_sec": ..., "speedup": ...},
      "interlayer": {"segments_per_sec_scalar": ..., "...batched": ...,
                     "dp_seconds_scalar": ..., "dp_seconds_batched": ...,
                     "dp_speedup_warm": ..., "dp_speedup_cold": ...,
                     "chain_costs_match": bool,
                     "resnet_solve_seconds": ...,
                     "transformer48_solve_seconds": ...},
      "solve": {"<net>": {"cold_seconds": ..., "warm_seconds": ...,
                          "energy_pj": ...}},
      "quick": bool
    }
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.cost_batch import FactorTable, evaluate_batch   # noqa: E402
from repro.core.cost_model import evaluate_layer                # noqa: E402
from repro.core.solver import memo, solve                       # noqa: E402
from repro.core.solver.exhaustive import iter_scheme_tables     # noqa: E402
from repro.core.solver.interlayer import (                      # noqa: E402
    dp_prioritize, dp_prioritize_scalar, enumerate_segments_scalar,
    segment_pool)
from repro.core.solver.intralayer import Constraints            # noqa: E402
from repro.hw.presets import eyeriss_multinode                  # noqa: E402
from repro.kernels.backend import configure_compile_cache       # noqa: E402
from repro.workloads.layers import conv                         # noqa: E402
from repro.workloads.nets import get_net, transformer           # noqa: E402

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def bench_cost_model(hw, n_schemes: int) -> dict:
    """Score the same candidate set scalar (one evaluate_layer call per
    scheme) and batched (vectorized), compare throughput.

    Candidates are the capacity-surviving lanes of the exhaustive
    enumeration — the actual solver workload (fully scored by both paths,
    no early-exit shortcuts for the scalar side)."""
    layer = conv("bench", 64, 96, 256, 27, 27, 5, 5)
    constr = Constraints(nodes=hw.node_array)
    tables = []
    lanes = 0
    for ft in iter_scheme_tables(layer, hw, constr, budget=10000):
        tables.append(ft)
        lanes += ft.batch
        if lanes >= n_schemes:
            break
    schemes = [ft.scheme_at(b) for ft in tables for b in range(ft.batch)]

    t0 = time.perf_counter()
    scalar = [evaluate_layer(s, hw, nodes_assigned=constr.num_nodes)
              for s in schemes]
    t_scalar = time.perf_counter() - t0

    evaluate_batch(tables[0], hw, nodes_assigned=constr.num_nodes)  # warmup
    t0 = time.perf_counter()
    results = [evaluate_batch(ft, hw, nodes_assigned=constr.num_nodes)
               for ft in tables]
    t_batch = time.perf_counter() - t0

    i = 0
    for res in results:
        for b in range(len(res)):
            assert scalar[i].valid == bool(res.valid[b]), \
                "batched/scalar validity disagreement"
            i += 1
    return {
        "schemes_scored": lanes,
        "scalar_schemes_per_sec": lanes / t_scalar,
        "batched_schemes_per_sec": lanes / t_batch,
        "speedup": t_scalar / t_batch,
    }


def _min_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_interlayer(hw, quick: bool) -> dict:
    """Batched inter-layer level vs the scalar PR-1 baseline on resnet
    (batch 64), plus end-to-end resnet + 48-block-transformer solve times.

    ``dp_speedup_cold`` is first-call-in-process (includes graph packing
    and alloc-table construction); ``dp_speedup_warm`` is the steady state
    (min over repeats), which is what repeated solves / annealing restarts
    and the k_S chain scoring actually see.
    """
    net = get_net("resnet", batch=64)
    n = len(net.layers)

    # --- DP prioritization (cold first: nothing warmed yet; the shared
    # alloc-option lru is re-cleared between the two cold runs so both
    # sides pay identical enumeration costs) ---------------------------------
    memo.clear_all()
    t0 = time.perf_counter()
    chains_b = dp_prioritize(net, hw)
    dp_cold_b = time.perf_counter() - t0
    memo.clear_all()
    t0 = time.perf_counter()
    chains_s = dp_prioritize_scalar(net, hw)
    dp_cold_s = time.perf_counter() - t0
    dp_warm_s = _min_of(lambda: dp_prioritize_scalar(net, hw),
                        2 if quick else 3)
    dp_warm_b = _min_of(lambda: dp_prioritize(net, hw), 3 if quick else 5)
    match = [c.est_cost for c in chains_b] == [c.est_cost for c in chains_s]

    # --- segment enumeration throughput (scalar vs one batched shot) -------
    # the batched side bypasses the per-graph CandidateBatch memo so this
    # times the actual enumerate+estimate+Pareto work, not a cache hit
    from repro.core.solver.interlayer import _build_candidate_batch
    n_segs = sum(len(enumerate_segments_scalar(net, hw, i))
                 for i in range(n))
    t_scalar_seg = _min_of(
        lambda: [enumerate_segments_scalar(net, hw, i) for i in range(n)],
        2 if quick else 3)
    t_batch_seg = _min_of(
        lambda: _build_candidate_batch(net, hw, list(range(n)), 4, None,
                                       True),
        2 if quick else 3)
    assert sum(len(v) for v in segment_pool(net, hw, range(n)).values()) \
        == n_segs, "batched/scalar segment count disagreement"

    # --- end-to-end solves (cold: process-wide caches cleared AND fresh
    # graph objects, since candidate batches are memoized on the graph) ----
    net_cold = get_net("resnet", batch=64)
    memo.clear_all()
    t0 = time.perf_counter()
    res_rn = solve(net_cold, hw)
    t_resnet = time.perf_counter() - t0
    tr = transformer(batch=64, layers=48)
    memo.clear_all()
    t0 = time.perf_counter()
    res_tr = solve(tr, hw)
    t_transformer = time.perf_counter() - t0
    assert res_rn.valid and res_tr.valid

    return {
        "net": "resnet/b64",
        "segments_enumerated": n_segs,
        "segments_per_sec_scalar": n_segs / t_scalar_seg,
        "segments_per_sec_batched": n_segs / t_batch_seg,
        "segment_speedup": t_scalar_seg / t_batch_seg,
        "dp_seconds_scalar": dp_warm_s,
        "dp_seconds_batched": dp_warm_b,
        "dp_speedup_warm": dp_warm_s / dp_warm_b,
        "dp_speedup_cold": dp_cold_s / dp_cold_b,
        "chain_costs_match": match,
        "resnet_solve_seconds": t_resnet,
        "transformer48_layers": len(tr.layers),
        "transformer48_solve_seconds": t_transformer,
    }


def bench_solve(hw, nets, batch: int) -> dict:
    out = {}
    for name in nets:
        net = get_net(name, batch=batch)
        memo.clear_all()
        t0 = time.perf_counter()
        cold = solve(net, hw)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = solve(net, hw)
        warm_s = time.perf_counter() - t0
        assert warm.total_energy_pj == cold.total_energy_pj
        out[name] = {"cold_seconds": cold_s, "warm_seconds": warm_s,
                     "energy_pj": cold.total_energy_pj,
                     "latency_cycles": cold.total_latency_cycles}
    return out


def _bench_fused(quick: bool) -> dict:
    """Fused-vs-interpret timing: the same ``NetworkPlan`` executed
    layer-by-layer in Pallas interpret mode and as fused compiled
    segments (min-of-N after a warm-up run each).  mlp + transformer2
    keep the interpret side affordable; their speedups feed the
    ``--min-fused-speedup`` regression gate."""
    import jax
    from repro.core.solver import solve
    from repro.lower import (lower_network, make_network_inputs,
                             network_runner)
    from repro.lower.calibrate import default_hw
    from repro.lower.fuse import cache_stats
    from repro.workloads.nets import get_net, transformer

    hw = default_hw()
    iters = 2 if quick else 3
    out = {"iters": iters, "nets": []}
    for net in [get_net("mlp", batch=4), transformer(batch=8, layers=2)]:
        sched = solve(net, hw)
        nplan = lower_network(sched, net, hw)
        inputs = make_network_inputs(nplan, 0)
        run_i = network_runner(nplan, inputs, backend="interpret")
        run_c = network_runner(nplan, inputs, backend="compiled",
                               keep="boundary")

        def best(run):
            jax.block_until_ready(run().outputs)        # warm-up/compile
            b = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(run().outputs)
                b = min(b, time.perf_counter() - t0)
            return b

        ti, tc = best(run_i), best(run_c)
        out["nets"].append({
            "net": net.name,
            "interpret_seconds": ti,
            "compiled_seconds": tc,
            "speedup": ti / tc,
        })
    out["min_speedup"] = min(e["speedup"] for e in out["nets"])
    out["executable_cache"] = cache_stats()
    return out


def bench_network(quick: bool, backend: str = "interpret") -> dict:
    """Network-tier pipeline: solve -> lower_network -> execute_network ->
    measure, per net (repro.lower.calibrate.run_network_calibration) on
    ``backend``, plus the fused-vs-interpret comparison.  The full
    per-net record goes to BENCH_network.json next to the other perf
    records; the main record keeps a summary."""
    from repro.lower.calibrate import run_network_calibration, save_record
    t0 = time.perf_counter()
    # 3 timed iters on the full sweep: the smallest nets run in ~0.3 s and
    # a single polluted sample can reorder them (the spearman gate)
    rec = run_network_calibration(quick=quick, iters=1 if quick else 3,
                                  backend=backend)
    rec["fused"] = _bench_fused(quick)
    rec["sweep_seconds"] = time.perf_counter() - t0
    save_record(rec, os.path.join(REPO_ROOT, "BENCH_network.json"))
    # include nets the sweep excluded for numerics, so --max-network-rel-err
    # fires on any divergence, not just sub-threshold ones
    errs = [e["max_rel_err"] for e in rec["nets"]] + \
        [s["max_rel_err"] for s in rec["skipped"] if "max_rel_err" in s]
    worst_err = max(errs, default=float("inf"))
    return {
        "backend": backend,
        "n_nets": rec["n_nets"],
        "n_skipped": len(rec["skipped"]),
        "nets": [e["net"] for e in rec["nets"]],
        "spearman_network": rec.get("spearman_network"),
        "worst_rel_err": worst_err,
        "total_forwarded": sum(e["n_forwarded"] for e in rec["nets"]),
        "fused": rec["fused"],
        "sweep_seconds": rec["sweep_seconds"],
    }


def bench_service(quick: bool) -> dict:
    """Schedule-service sweep: cold vs warm vs cached solve latency on
    resnet/b64 through a fresh store, then measured top-k autotuning (the
    acceptance workload: lower + execute k candidates per net, promote the
    measured winner).  Full record -> BENCH_service.json; the main record
    keeps a summary."""
    import shutil
    import tempfile
    from repro.lower.calibrate import default_hw, save_record
    from repro.service import LocalClient, ScheduleStore, autotune_network
    from repro.workloads.nets import transformer as transformer_net

    hw = eyeriss_multinode()
    root = tempfile.mkdtemp(prefix="repro-service-bench-")
    try:
        client = LocalClient(ScheduleStore(root))
        # cold: fresh process caches + fresh graph objects (candidate
        # batches are memoized on the graph)
        memo.clear_all()
        r_cold = client.solve(get_net("resnet", batch=64), hw)
        assert r_cold.source == "cold" and r_cold.schedule.valid
        # warm: family near-miss (same net, batch 32) seeds the solve; its
        # fair baseline is a cold batch-32 solve in a fresh store
        memo.clear_all()
        t0 = time.perf_counter()
        cold32 = solve(get_net("resnet", batch=32), hw)
        cold32_s = time.perf_counter() - t0
        assert cold32.valid
        memo.clear_all()
        r_warm = client.solve(get_net("resnet", batch=32), hw)
        # cached: the batch-64 signature again, process caches cold
        memo.clear_all()
        r_cached = client.solve(get_net("resnet", batch=64), hw)
        assert r_cached.source == "cached"
        assert r_cached.schedule.total_energy_pj == \
            r_cold.schedule.total_energy_pj
        record = {
            "net": "resnet/b64",
            "cold_seconds": r_cold.seconds,
            "cached_seconds": r_cached.seconds,
            "cached_speedup": r_cold.seconds / r_cached.seconds,
            "warm_net": "resnet/b32",
            "warm_source": r_warm.source,
            "warm_seconds": r_warm.seconds,
            "warm_cold_baseline_seconds": cold32_s,
            "warm_speedup": cold32_s / r_warm.seconds,
            "warm_energy_ratio_vs_cold":
                r_warm.schedule.total_energy_pj / cold32.total_energy_pj,
            "store": client.stats(),
        }
        # measured top-k autotuning on the small-grid execution hardware
        hw_exec = default_hw()
        nets = [get_net("mlp", batch=4)]
        if not quick:
            nets.append(transformer_net(batch=8, layers=2))
        at = []
        for net in nets:
            rep = autotune_network(net, hw_exec, store=client.store, k=3,
                                   iters=1 if quick else 2)
            at.append({k: rep.get(k) for k in (
                "net", "n_candidates", "n_executed", "rank_agreement",
                "promoted_rank", "promoted_measured_seconds",
                "argmin_measured_seconds", "autotune_seconds", "skipped")})
        record["autotune"] = at
        save_record(record, os.path.join(REPO_ROOT, "BENCH_service.json"))
        return record
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _pct(vals, q: float):
    if not vals:
        return None
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]


def bench_chaos(quick: bool) -> dict:
    """Resilience under a seeded fault schedule (the acceptance workload):
    a burst of requests through the async ``SolveServer`` while ~20% of
    store reads/writes raise, a fraction of segment solves run slow, and
    one request carries an already-expired deadline.  Availability is the
    fraction of requests answered with a ``ServiceResult`` or the typed
    ``ServiceError`` (anything else — a hang or an untyped crash — counts
    against it); latency percentiles are measured from submission.  Full
    record -> BENCH_robustness.json."""
    import asyncio
    import dataclasses
    import shutil
    import tempfile
    from repro.lower.calibrate import save_record
    from repro.runtime.fault import CircuitBreaker, RecoveryPolicy
    from repro.runtime.inject import FaultPlan, FaultSpec, inject
    from repro.service import (ScheduleStore, ServiceError, ServiceResult,
                               SolveRequest, SolveServer,
                               serve_batch_settled)

    hw = eyeriss_multinode()
    n_requests = 20 if quick else 50
    specs = {
        "store.read": FaultSpec(rate=0.20, kind="error"),
        "store.write": FaultSpec(rate=0.20, kind="error"),
        "solve.segment": FaultSpec(rate=0.10, kind="slow", delay_s=0.02),
    }
    plan = FaultPlan.make(20260807, specs)
    mix = [("mlp", 8), ("mlp", 16), ("lstm", 8), ("mlp", 32)]
    reqs = [SolveRequest.make(get_net(n, batch=b), hw)
            for n, b in (mix[i % len(mix)] for i in range(n_requests - 1))]
    # one rushed request exercises the deadline -> greedy floor
    reqs.append(SolveRequest.make(get_net("lstm", batch=16), hw,
                                  deadline_s=1e-4))
    root = tempfile.mkdtemp(prefix="repro-chaos-bench-")
    try:
        server = SolveServer(
            ScheduleStore(root),
            breaker=CircuitBreaker(threshold=3, cooldown_s=0.2),
            retry_policy=RecoveryPolicy(max_retries=3,
                                        backoff_seconds=0.005,
                                        max_backoff=0.05),
            batch_window_s=0.002)
        memo.clear_all()
        t0 = time.perf_counter()
        with inject(plan) as inj:
            out = asyncio.run(asyncio.wait_for(
                serve_batch_settled(server, reqs), timeout=600))
        wall = time.perf_counter() - t0
        results = [r for r in out if isinstance(r, ServiceResult)]
        typed_errors = [r for r in out if isinstance(r, ServiceError)]
        assert all(r.schedule.valid for r in results), \
            "chaos run served an invalid schedule"
        lat = [r.seconds for r in results]
        paths = {
            "store_faults_survived":
                inj.fired.get("store.read", 0) +
                inj.fired.get("store.write", 0),
            "slow_solves_injected": inj.fired.get("solve.segment", 0),
            "greedy_served":
                sum(1 for r in results if r.source == "greedy"),
            "degraded_flagged": sum(1 for r in results if r.degraded),
            "breaker_opens": server.stats()["breaker"]["opens"],
            "typed_errors": len(typed_errors),
        }
        record = {
            "n_requests": len(reqs),
            "availability":
                (len(results) + len(typed_errors)) / len(reqs),
            "n_results": len(results),
            "n_typed_errors": len(typed_errors),
            "n_degraded": paths["degraded_flagged"],
            "p50_seconds": _pct(lat, 0.50),
            "p99_seconds": _pct(lat, 0.99),
            "max_seconds": max(lat, default=None),
            "wall_seconds": wall,
            "fault_plan": {"seed": plan.seed,
                           "specs": {s: dataclasses.asdict(sp)
                                     for s, sp in specs.items()}},
            "injected": inj.summary(),
            "paths": paths,
            # distinct degradation mechanisms this schedule exercised
            "paths_exercised": sum(
                1 for k in ("store_faults_survived",
                            "slow_solves_injected", "greedy_served")
                if paths[k] > 0),
            "server": server.stats(),
        }
        save_record(record,
                    os.path.join(REPO_ROOT, "BENCH_robustness.json"))
        return record
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_multinode(quick: bool) -> dict:
    """Elastic multi-node chaos bench (the acceptance workload): solve +
    lower mlp once, partition the segment chain across a 4-node mesh
    (``multinode.plan_multinode``), then serve a burst of requests through
    the resilient ``MeshExecutor`` twice — fault-free, and with one node
    killed mid-run plus another slowed 5x (seeded ``runtime.inject``
    schedule).  Availability is the fraction of chaos requests that
    completed; non-degraded results must be bit-identical to the
    fault-free run; re-partitions must re-solve only the dirty segments
    (count reported).  Full record -> BENCH_multinode.json."""
    import dataclasses
    import hashlib
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from repro.core.solver.multinode import NodeMesh, plan_multinode
    from repro.lower.calibrate import default_hw, save_record
    from repro.lower.meshexec import MeshExecutor, build_segment_tasks
    from repro.lower.netexec import make_network_inputs
    from repro.runtime.inject import FaultPlan, FaultSpec, inject

    hw = default_hw()
    n_nodes = 4
    n_requests = 6 if quick else 16
    net = get_net("mlp", batch=4)
    memo.clear_all()
    t0 = time.perf_counter()
    sched = solve(net, hw, max_seg_len=2)
    solve_s = time.perf_counter() - t0
    assert sched.valid
    nplan = sched.lower(net, hw)
    t0 = time.perf_counter()
    plan = plan_multinode(sched, net, hw, NodeMesh(nodes=n_nodes))
    plan_s = time.perf_counter() - t0
    base = make_network_inputs(nplan, seed=0)
    weights = {k: v for k, v in base.items() if k.endswith(".W")}
    ext = [{k: np.asarray(v)
            for k, v in make_network_inputs(nplan, seed=i).items()
            if k.endswith(".I")} for i in range(n_requests)]
    tasks = build_segment_tasks(nplan, weights)

    def digest(outputs) -> str:
        h = hashlib.sha256()
        for k in sorted(outputs):
            h.update(k.encode())
            h.update(np.ascontiguousarray(outputs[k]).tobytes())
        return h.hexdigest()

    def serve(faults=None):
        """One burst through a fresh executor; returns per-request
        (digest, seconds, degraded) plus the executor's stats."""
        with MeshExecutor(plan, tasks, schedule=sched, graph=net,
                          hw=hw) as ex:
            def one(i):
                t0 = time.perf_counter()
                try:
                    r = ex.run(ext[i], f"req{i}")
                except Exception as e:      # an unanswered request counts
                    return None, time.perf_counter() - t0, repr(e)
                return digest(r.outputs), \
                    time.perf_counter() - t0, r.degraded
            if faults is not None:
                with inject(faults) as inj:
                    with ThreadPoolExecutor(max_workers=2) as tp:
                        rows = list(tp.map(one, range(n_requests)))
                fired = inj.summary()
            else:
                with ThreadPoolExecutor(max_workers=2) as tp:
                    rows = list(tp.map(one, range(n_requests)))
                fired = {}
            return rows, ex.stats(), fired

    # fault-free reference (also the bit-identity oracle)
    t0 = time.perf_counter()
    ref_rows, ref_stats, _ = serve()
    ref_wall = time.perf_counter() - t0
    assert not any(d for _, _, d in ref_rows)

    # chaos: the crashed node's 3rd task kills it permanently; a second
    # node (a surviving replica) runs everything 5x slow
    victim = plan.parts[0].node_ids[0]
    slow = next((n for p in plan.parts for n in p.node_ids
                 if n != victim), (victim + 1) % n_nodes)
    specs = {
        "node.crash": FaultSpec(rate=1.0, kind="error",
                                match=f"node{victim}", after=2),
        "node.slow": FaultSpec(rate=1.0, kind="slow",
                               match=f"node{slow}", factor=5.0),
    }
    faults = FaultPlan.make(20260808, specs)
    t0 = time.perf_counter()
    rows, stats, fired = serve(faults)
    wall = time.perf_counter() - t0

    done = [(h, s, d) for h, s, d in rows if h is not None]
    lat = [s for _, s, _ in done]
    n_done = len(done)
    n_degraded = sum(1 for h, _, d in rows if h is not None and d)
    identical = all(h == rh for (h, _, d), (rh, _, _)
                    in zip(rows, ref_rows) if h is not None and not d)
    record = {
        "net": "mlp/b4",
        "n_nodes": n_nodes,
        "n_segments": plan.n_segments,
        "n_requests": n_requests,
        "availability": n_done / n_requests,
        "n_degraded": n_degraded,
        "bit_identical_non_degraded": identical,
        "p50_seconds": _pct(lat, 0.50),
        "p99_seconds": _pct(lat, 0.99),
        "baseline_p50_seconds": _pct([s for _, s, _ in ref_rows], 0.50),
        "recovery_seconds": stats["recovery_seconds"],
        "repartitions": stats["repartitions"],
        "resolved_segments": stats["resolved_segments"],
        "failures": stats["failures"],
        "replays": stats["replays"],
        "backups": stats["backups"],
        "alive_nodes": stats["alive_nodes"],
        "single_node_fallback": stats["fallback"],
        "solve_seconds": solve_s,
        "plan_seconds": plan_s,
        "plan": plan.to_json(),
        "wall_seconds": wall,
        "baseline_wall_seconds": ref_wall,
        "fault_plan": {"seed": faults.seed,
                       "specs": {s: dataclasses.asdict(sp)
                                 for s, sp in specs.items()}},
        "injected": fired,
        "errors": [d for h, _, d in rows if h is None],
        "baseline_stats": ref_stats,
    }
    save_record(record, os.path.join(REPO_ROOT, "BENCH_multinode.json"))
    return record


def bench_obs(quick: bool) -> dict:
    """Observability bench: instrumentation overhead and the chaos trace.

    Part 1 times the resnet/b64 cold solve in three modes, interleaved
    min-of-N so machine drift hits every mode equally: ``obs.off()``
    (true zero-observability baseline), the production default (metrics
    on, tracing disabled — the "disabled-mode" the <=2% gate guards),
    and metrics + tracing enabled (<=10% gate).  Part 2 replays the
    multi-node chaos recipe (node killed mid-serve + a 5x-slow peer,
    seeded injection) with tracing on and a hair-trigger straggler
    detector, exports the Chrome trace to TRACE_obs.json and checks the
    node kill, backup dispatch and repartition all appear as annotated
    events.  Full record -> BENCH_obs.json."""
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from repro import obs
    from repro.core.solver.multinode import NodeMesh, plan_multinode
    from repro.lower.calibrate import default_hw, save_record
    from repro.lower.meshexec import MeshExecutor, build_segment_tasks
    from repro.lower.netexec import make_network_inputs
    from repro.obs import trace
    from repro.obs.metrics import REGISTRY
    from repro.runtime.inject import FaultPlan, FaultSpec, inject
    from repro.runtime.straggler import StragglerDetector

    hw = default_hw()
    repeats = 7 if quick else 9
    net = get_net("resnet", batch=64)

    def cold_solve():
        memo.clear_all()
        sched = solve(net, hw)
        assert sched.valid

    # several solves per timed sample: a single ~0.15s cold solve is
    # inside this machine class's scheduler-noise floor (+-30% per-round
    # swings), far too coarse to resolve a 2% overhead; amortizing 3
    # solves per sample plus min-of-N gets the estimate under 1%
    inner = 4

    def timed(mode: str) -> float:
        if mode == "off":
            obs.off()
        elif mode == "tracing":
            obs.on()
            trace.enable()
        else:                       # "metrics": the production default
            obs.on()
        try:
            t0 = time.perf_counter()
            for _ in range(inner):
                cold_solve()
            return (time.perf_counter() - t0) / inner
        finally:
            trace.disable()         # drop the throwaway overhead trace
            obs.on()

    cold_solve()                    # warm imports/JIT-ish one-time costs
    modes = ("off", "metrics", "tracing")
    best = {m: float("inf") for m in modes}
    for _ in range(repeats):
        for m in modes:
            best[m] = min(best[m], timed(m))
    # report the *signed* raw deltas: min-of-N jitter can make an
    # instrumented run measure "faster" than the baseline, and hiding
    # that (the old max(0, ...) here) also hid how noisy the measurement
    # was.  The CI gate clamps at comparison time instead.
    disabled_overhead = best["metrics"] / best["off"] - 1.0
    enabled_overhead = best["tracing"] / best["off"] - 1.0

    # -- part 2: traced multi-node chaos run --------------------------------
    n_nodes = 4
    n_requests = 8 if quick else 16
    mnet = get_net("mlp", batch=4)
    memo.clear_all()
    msched = solve(mnet, hw, max_seg_len=2)
    assert msched.valid
    nplan = msched.lower(mnet, hw)
    plan = plan_multinode(msched, mnet, hw, NodeMesh(nodes=n_nodes))
    base = make_network_inputs(nplan, seed=0)
    weights = {k: v for k, v in base.items() if k.endswith(".W")}
    ext = [{k: np.asarray(v)
            for k, v in make_network_inputs(nplan, seed=i).items()
            if k.endswith(".I")} for i in range(n_requests)]
    tasks = build_segment_tasks(nplan, weights)
    # the slow node draws backup races; backups go to the lowest-id
    # healthy node.  The crash victim must be neither — a crash landing
    # on a backup dispatch is absorbed by the race (the primary's result
    # wins) and never surfaces as the NodeFailure that drives the
    # repartition rung, which this trace must show
    slow = 1
    victim = 2
    specs = {
        "node.crash": FaultSpec(rate=1.0, kind="error",
                                match=f"node{victim}", after=2),
        "node.slow": FaultSpec(rate=1.0, kind="slow",
                               match=f"node{slow}", factor=5.0),
    }
    faults = FaultPlan.make(20260808, specs)
    # hair-trigger detector (vs the 2.0x/warmup-2 default) so the 5x-slow
    # node is flagged early enough for a backup race to appear in-trace
    detector = StragglerDetector(factor=1.5, warmup=1)
    trace_path = os.path.join(REPO_ROOT, "TRACE_obs.json")

    t0 = time.perf_counter()
    with trace.tracing(trace_path) as tr:
        with MeshExecutor(plan, tasks, schedule=msched, graph=mnet,
                          hw=hw, detector=detector) as ex:
            def one(i):
                try:
                    r = ex.run(ext[i], f"req{i}")
                except Exception as e:
                    return None, repr(e)
                return True, r.degraded
            with inject(faults) as inj:
                with ThreadPoolExecutor(max_workers=2) as tp:
                    rows = list(tp.map(one, range(n_requests)))
            fired = inj.summary()
            mesh_stats = ex.stats()
    mesh_wall = time.perf_counter() - t0

    # re-load the exported file: the acceptance check is on what a viewer
    # would actually see, not on the in-memory buffer
    summary = trace.summarize_events(trace.load_events(trace_path))
    required = ("mesh.node_killed", "mesh.backup_dispatch",
                "mesh.repartition", "fault.injected")
    event_counts = {n: summary["instants"].get(n, 0) for n in required}
    missing = [n for n in required if event_counts[n] == 0]

    n_done = sum(1 for ok, _ in rows if ok)
    record = {
        "net": "resnet/b64",
        "repeats": repeats,
        "inner_solves": inner,
        "solve_seconds": dict(best),
        "disabled_overhead": disabled_overhead,
        "enabled_overhead": enabled_overhead,
        "mesh": {
            "net": "mlp/b4",
            "n_nodes": n_nodes,
            "n_requests": n_requests,
            "availability": n_done / n_requests,
            "n_degraded": sum(1 for ok, d in rows if ok and d),
            "errors": [d for ok, d in rows if not ok],
            "wall_seconds": mesh_wall,
            "repartitions": mesh_stats["repartitions"],
            "backups": mesh_stats["backups"],
            "failures": mesh_stats["failures"],
            "detector": {"factor": detector.factor,
                         "warmup": detector.warmup},
            "fault_plan": {"seed": faults.seed,
                           "specs": {s: dataclasses.asdict(sp)
                                     for s, sp in specs.items()}},
            "injected": fired,
        },
        "trace": {
            "path": os.path.relpath(trace_path, REPO_ROOT),
            "n_events": summary["n_events"],
            "dropped": tr.dropped,
            "spans": {k: v["count"] for k, v in summary["spans"].items()},
            "instants": summary["instants"],
        },
        "required_events": event_counts,
        "missing_events": missing,
        "n_metric_families": len(REGISTRY.names()),
    }
    save_record(record, os.path.join(REPO_ROOT, "BENCH_obs.json"))
    return record


def bench_calibration(quick: bool) -> dict:
    """Solver -> lowering -> pallas execution -> measured-vs-predicted
    calibration sweep (repro.lower.calibrate).  The full per-pair record is
    written to BENCH_calibration.json next to BENCH_solver.json; the main
    record keeps a summary."""
    from repro.lower.calibrate import run_calibration, save_record
    t0 = time.perf_counter()
    rec = run_calibration(quick=quick, iters=1 if quick else 2)
    rec["sweep_seconds"] = time.perf_counter() - t0
    save_record(rec, os.path.join(REPO_ROOT, "BENCH_calibration.json"))
    worst_err = max((p.get("rel_err", 0.0) for p in rec["pairs"]),
                    default=float("inf"))
    return {
        "n_pairs": rec["n_pairs"],
        "n_skipped": len(rec["skipped"]),
        "spearman_raw": rec.get("spearman_raw"),
        "spearman_calibrated": rec.get("spearman_calibrated"),
        "worst_rel_err": worst_err,
        "coefficients": rec.get("calibration"),
        "sweep_seconds": rec["sweep_seconds"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small sample counts / one net (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON record here "
                    "(always printed to stdout)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="exit nonzero if batched/scalar cost-model speedup "
                    "is below this (regression gate)")
    ap.add_argument("--min-interlayer-speedup", type=float, default=None,
                    help="exit nonzero if the warm batched/scalar "
                    "dp_prioritize speedup is below this")
    ap.add_argument("--max-transformer-seconds", type=float, default=None,
                    help="exit nonzero if the 48-block transformer cold "
                    "solve exceeds this time budget")
    ap.add_argument("--calibrate", action="store_true",
                    help="also run the lowering/calibration sweep (writes "
                    "BENCH_calibration.json)")
    ap.add_argument("--calibrate-only", action="store_true",
                    help="run ONLY the lowering/calibration sweep (the CI "
                    "lowering smoke gate)")
    ap.add_argument("--min-calibration-spearman", type=float, default=None,
                    help="exit nonzero if predicted-vs-measured Spearman "
                    "rank correlation is below this")
    ap.add_argument("--min-calibration-pairs", type=int, default=None,
                    help="exit nonzero if the calibration sweep produced "
                    "fewer (scheme, layer) pairs than this")
    ap.add_argument("--network", action="store_true",
                    help="also run the network-execution sweep (writes "
                    "BENCH_network.json)")
    ap.add_argument("--network-only", action="store_true",
                    help="run ONLY the network-execution sweep (the CI "
                    "network smoke gate)")
    ap.add_argument("--min-network-nets", type=int, default=None,
                    help="exit nonzero if fewer nets executed end-to-end "
                    "than this")
    ap.add_argument("--max-network-rel-err", type=float, default=None,
                    help="exit nonzero if any executed net's worst "
                    "per-layer rel error vs the whole-graph reference "
                    "exceeds this")
    ap.add_argument("--min-network-spearman", type=float, default=None,
                    help="exit nonzero if network-level predicted-vs-"
                    "measured Spearman is below this")
    ap.add_argument("--backend", default="interpret",
                    choices=["interpret", "pallas", "compiled"],
                    help="execution backend for the network sweep "
                    "(BENCH_network.json records it; the fused-vs-"
                    "interpret comparison always runs both)")
    ap.add_argument("--min-fused-speedup", type=float, default=None,
                    help="exit nonzero if fused compiled execution is "
                    "not at least this many times faster than "
                    "layer-by-layer interpret on every comparison net")
    ap.add_argument("--service", action="store_true",
                    help="also run the schedule-service sweep (writes "
                    "BENCH_service.json)")
    ap.add_argument("--service-only", action="store_true",
                    help="run ONLY the schedule-service sweep (the CI "
                    "service smoke gate)")
    ap.add_argument("--min-service-cached-speedup", type=float,
                    default=None,
                    help="exit nonzero if the store-cached resnet/b64 "
                    "solve is not at least this much faster than cold")
    ap.add_argument("--min-autotune-candidates", type=int, default=None,
                    help="exit nonzero if any autotuned net executed "
                    "fewer candidates than this")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the resilience sweep under injected "
                    "faults (writes BENCH_robustness.json)")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run ONLY the resilience sweep (the CI chaos "
                    "smoke gate)")
    ap.add_argument("--min-chaos-availability", type=float, default=None,
                    help="exit nonzero if the fraction of chaos requests "
                    "answered (result or typed error) is below this")
    ap.add_argument("--max-chaos-p99", type=float, default=None,
                    help="exit nonzero if p99 request latency under "
                    "injected faults exceeds this many seconds")
    ap.add_argument("--min-chaos-degraded-paths", type=int, default=None,
                    help="exit nonzero if fewer distinct degradation "
                    "paths were exercised than this")
    ap.add_argument("--multinode", action="store_true",
                    help="also run the multi-node chaos sweep: node kill "
                    "+ 5x slowdown mid-serve (writes BENCH_multinode.json)")
    ap.add_argument("--multinode-only", action="store_true",
                    help="run ONLY the multi-node chaos sweep (the CI "
                    "multi-node smoke gate)")
    ap.add_argument("--min-multinode-availability", type=float,
                    default=None,
                    help="exit nonzero if the fraction of requests "
                    "completed under node kill/slowdown is below this")
    ap.add_argument("--require-multinode-identical", action="store_true",
                    help="exit nonzero unless every non-degraded chaos "
                    "request's outputs are bit-identical to the "
                    "fault-free run")
    ap.add_argument("--obs", action="store_true",
                    help="also run the observability sweep: instrumentation "
                    "overhead + traced multi-node chaos run (writes "
                    "BENCH_obs.json and TRACE_obs.json)")
    ap.add_argument("--obs-only", action="store_true",
                    help="run ONLY the observability sweep (the CI obs "
                    "smoke gate)")
    ap.add_argument("--max-obs-disabled-overhead", type=float, default=None,
                    help="exit nonzero if the default mode (metrics on, "
                    "tracing disabled) slows the resnet/b64 cold solve by "
                    "more than this fraction vs obs.off(), e.g. 0.02")
    ap.add_argument("--max-obs-enabled-overhead", type=float, default=None,
                    help="exit nonzero if metrics + tracing slow the "
                    "resnet/b64 cold solve by more than this fraction vs "
                    "obs.off(), e.g. 0.10")
    ap.add_argument("--require-obs-events", action="store_true",
                    help="exit nonzero unless the traced chaos run's "
                    "exported trace shows the node kill, backup dispatch, "
                    "repartition and injected faults as events")
    args = ap.parse_args(argv)
    configure_compile_cache()
    only = args.calibrate_only or args.network_only or args.service_only \
        or args.chaos_only or args.multinode_only or args.obs_only
    if only and (args.min_speedup is not None
                 or args.min_interlayer_speedup is not None
                 or args.max_transformer_seconds is not None):
        ap.error("--calibrate-only/--network-only/--service-only skip the "
                 "solver benches; drop them or drop the solver gate flags")

    hw = eyeriss_multinode()
    n_schemes = 2000 if args.quick else 20000
    nets = ["mlp"] if args.quick else ["mlp", "alexnet", "lstm", "mobilenet"]

    if args.calibrate_only:
        record = {"quick": args.quick,
                  "calibration": bench_calibration(args.quick)}
    elif args.network_only:
        record = {"quick": args.quick,
                  "network": bench_network(args.quick, args.backend)}
    elif args.service_only:
        record = {"quick": args.quick,
                  "service": bench_service(args.quick)}
    elif args.chaos_only:
        record = {"quick": args.quick,
                  "chaos": bench_chaos(args.quick)}
    elif args.multinode_only:
        record = {"quick": args.quick,
                  "multinode": bench_multinode(args.quick)}
    elif args.obs_only:
        record = {"quick": args.quick,
                  "obs": bench_obs(args.quick)}
    else:
        record = {
            "quick": args.quick,
            "hw": hw.name,
            "cost_model": bench_cost_model(hw, n_schemes),
            "interlayer": bench_interlayer(hw, args.quick),
            "solve": bench_solve(hw, nets, batch=64),
            "memo": memo.stats(),
        }
        if args.calibrate:
            record["calibration"] = bench_calibration(args.quick)
        if args.network:
            record["network"] = bench_network(args.quick, args.backend)
        if args.service:
            record["service"] = bench_service(args.quick)
        if args.chaos:
            record["chaos"] = bench_chaos(args.quick)
        if args.multinode:
            record["multinode"] = bench_multinode(args.quick)
        if args.obs:
            record["obs"] = bench_obs(args.quick)
    text = json.dumps(record, indent=2)
    print(text)
    # BENCH_solver.json at the repo root is the perf-trajectory record
    # (kept intact by calibration-/network-only runs, which have their own)
    paths = [args.out] if only else \
        [os.path.join(REPO_ROOT, "BENCH_solver.json"), args.out]
    for path in filter(None, paths):
        with open(path, "w") as f:
            f.write(text + "\n")

    fails = []
    cal = record.get("calibration")
    if args.min_calibration_spearman is not None:
        if cal is None:
            fails.append("calibration gate set but sweep did not run "
                         "(pass --calibrate)")
        elif cal["spearman_raw"] is None:
            fails.append(f"calibration produced too few valid pairs "
                         f"({cal['n_pairs']}) to compute spearman")
        elif cal["spearman_raw"] < args.min_calibration_spearman:
            fails.append(f"calibration spearman {cal['spearman_raw']:.3f} "
                         f"< {args.min_calibration_spearman}")
    if args.min_calibration_pairs is not None and cal is not None and \
            cal["n_pairs"] < args.min_calibration_pairs:
        fails.append(f"calibration pairs {cal['n_pairs']} < "
                     f"{args.min_calibration_pairs}")
    nw = record.get("network")
    if args.min_network_nets is not None:
        if nw is None:
            fails.append("network gate set but sweep did not run "
                         "(pass --network)")
        elif nw["n_nets"] < args.min_network_nets:
            fails.append(f"network execution covered {nw['n_nets']} nets < "
                         f"{args.min_network_nets} "
                         f"(skipped: {nw['n_skipped']})")
    if args.max_network_rel_err is not None:
        if nw is None:
            fails.append("network rel-err gate set but sweep did not run "
                         "(pass --network)")
        elif nw["worst_rel_err"] > args.max_network_rel_err:
            fails.append(f"network worst rel err {nw['worst_rel_err']:.2e} "
                         f"> {args.max_network_rel_err}")
    if args.min_network_spearman is not None:
        if nw is None:
            fails.append("network spearman gate set but sweep did not run "
                         "(pass --network)")
        elif nw["spearman_network"] is None:
            fails.append("network sweep produced too few nets for spearman")
        elif nw["spearman_network"] < args.min_network_spearman:
            fails.append(f"network spearman {nw['spearman_network']:.3f} < "
                         f"{args.min_network_spearman}")
    if args.min_fused_speedup is not None:
        if nw is None:
            fails.append("fused speedup gate set but sweep did not run "
                         "(pass --network)")
        elif nw["fused"]["min_speedup"] < args.min_fused_speedup:
            worst = min(nw["fused"]["nets"], key=lambda e: e["speedup"])
            fails.append(f"fused speedup {worst['speedup']:.1f}x on "
                         f"{worst['net']} < {args.min_fused_speedup}x")
    sv = record.get("service")
    if args.min_service_cached_speedup is not None:
        if sv is None:
            fails.append("service gate set but sweep did not run "
                         "(pass --service)")
        elif sv["cached_speedup"] < args.min_service_cached_speedup:
            fails.append(f"service cached speedup "
                         f"{sv['cached_speedup']:.1f}x < "
                         f"{args.min_service_cached_speedup}x")
    if args.min_autotune_candidates is not None:
        if sv is None:
            fails.append("autotune gate set but sweep did not run "
                         "(pass --service)")
        else:
            worst = min((a["n_executed"] for a in sv["autotune"]),
                        default=0)
            if worst < args.min_autotune_candidates:
                fails.append(f"autotune executed {worst} candidates < "
                             f"{args.min_autotune_candidates}")
            bad = [a["net"] for a in sv["autotune"]
                   if a.get("argmin_measured_seconds") is not None
                   and a["promoted_measured_seconds"]
                   > a["argmin_measured_seconds"]]
            if bad:
                fails.append("autotune promoted slower-than-argmin "
                             f"schedules on {bad}")
    ch = record.get("chaos")
    if args.min_chaos_availability is not None:
        if ch is None:
            fails.append("chaos availability gate set but sweep did not "
                         "run (pass --chaos)")
        elif ch["availability"] < args.min_chaos_availability:
            fails.append(f"chaos availability {ch['availability']:.3f} < "
                         f"{args.min_chaos_availability} "
                         f"({ch['n_requests'] - ch['n_results'] - ch['n_typed_errors']} unanswered)")
    if args.max_chaos_p99 is not None:
        if ch is None:
            fails.append("chaos p99 gate set but sweep did not run "
                         "(pass --chaos)")
        elif ch["p99_seconds"] is None or \
                ch["p99_seconds"] > args.max_chaos_p99:
            fails.append(f"chaos p99 latency {ch['p99_seconds']}s > "
                         f"{args.max_chaos_p99}s budget")
    if args.min_chaos_degraded_paths is not None:
        if ch is None:
            fails.append("chaos degraded-paths gate set but sweep did "
                         "not run (pass --chaos)")
        elif ch["paths_exercised"] < args.min_chaos_degraded_paths:
            fails.append(f"chaos exercised {ch['paths_exercised']} "
                         f"degradation paths < "
                         f"{args.min_chaos_degraded_paths}")
    mn = record.get("multinode")
    if args.min_multinode_availability is not None:
        if mn is None:
            fails.append("multi-node availability gate set but sweep did "
                         "not run (pass --multinode)")
        elif mn["availability"] < args.min_multinode_availability:
            fails.append(
                f"multi-node availability {mn['availability']:.3f} < "
                f"{args.min_multinode_availability} "
                f"(errors: {mn['errors']})")
    if args.require_multinode_identical:
        if mn is None:
            fails.append("multi-node bit-identity gate set but sweep did "
                         "not run (pass --multinode)")
        elif not mn["bit_identical_non_degraded"]:
            fails.append("multi-node chaos outputs diverged from the "
                         "fault-free run on non-degraded requests")
    ob = record.get("obs")
    if args.max_obs_disabled_overhead is not None:
        if ob is None:
            fails.append("obs disabled-overhead gate set but sweep did "
                         "not run (pass --obs)")
        # the record keeps signed raw deltas; the gate clamps negative
        # jitter ("instrumented was faster") to zero when comparing
        elif max(0.0, ob["disabled_overhead"]) > \
                args.max_obs_disabled_overhead:
            fails.append(
                f"obs disabled-mode overhead "
                f"{ob['disabled_overhead']:.4f} > "
                f"{args.max_obs_disabled_overhead} (metrics-on solve "
                f"{ob['solve_seconds']['metrics']:.3f}s vs off "
                f"{ob['solve_seconds']['off']:.3f}s)")
    if args.max_obs_enabled_overhead is not None:
        if ob is None:
            fails.append("obs enabled-overhead gate set but sweep did "
                         "not run (pass --obs)")
        elif max(0.0, ob["enabled_overhead"]) > \
                args.max_obs_enabled_overhead:
            fails.append(
                f"obs tracing-enabled overhead "
                f"{ob['enabled_overhead']:.4f} > "
                f"{args.max_obs_enabled_overhead} (traced solve "
                f"{ob['solve_seconds']['tracing']:.3f}s vs off "
                f"{ob['solve_seconds']['off']:.3f}s)")
    if args.require_obs_events:
        if ob is None:
            fails.append("obs event gate set but sweep did not run "
                         "(pass --obs)")
        elif ob["missing_events"]:
            fails.append("obs chaos trace is missing required events: "
                         f"{ob['missing_events']} "
                         f"(got {ob['required_events']})")
    if only:
        for f_ in fails:
            print("FAIL:", f_, file=sys.stderr)
        return 1 if fails else 0

    il = record["interlayer"]
    if not il["chain_costs_match"]:
        fails.append("inter-layer parity: batched chain costs != scalar")
    if args.min_speedup is not None and \
            record["cost_model"]["speedup"] < args.min_speedup:
        fails.append(f"cost-model speedup "
                     f"{record['cost_model']['speedup']:.1f}x < "
                     f"{args.min_speedup}x")
    if args.min_interlayer_speedup is not None:
        # gate both the (memoized) DP steady state and the raw un-cached
        # estimator throughput, so a regression in either shows up
        if il["dp_speedup_warm"] < args.min_interlayer_speedup:
            fails.append(f"interlayer dp speedup "
                         f"{il['dp_speedup_warm']:.1f}x < "
                         f"{args.min_interlayer_speedup}x")
        if il["segment_speedup"] < args.min_interlayer_speedup:
            fails.append(f"interlayer segment speedup "
                         f"{il['segment_speedup']:.1f}x < "
                         f"{args.min_interlayer_speedup}x")
    if args.max_transformer_seconds is not None and \
            il["transformer48_solve_seconds"] > args.max_transformer_seconds:
        fails.append(f"transformer48 solve "
                     f"{il['transformer48_solve_seconds']:.2f}s > "
                     f"{args.max_transformer_seconds}s budget")
    for f_ in fails:
        print("FAIL:", f_, file=sys.stderr)
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
