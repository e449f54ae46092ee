"""Run a ``NetworkPlan`` end to end: the graph walk, the network's
inputs, the per-layer Pallas chain and the whole-graph reference.

**The graph walk** (``layer_output``) computes one layer's output from
its in-graph source values, or a graph source's ``.I`` feed, on a map
from kind to kernel.  The three executors share it: the whole-graph
reference (``reference_network``, on ``exec.ORACLE_KERNELS``), the
per-layer Pallas chain (``network_runner`` under ``interpret`` or
``pallas``, and ``meshexec``'s per-layer tasks, on
``exec.pallas_kernels``), and the fused trace (``fuse``, on
``fuse.compiled_kernels``).  What a layer kind feeds and holds comes
from the kind table (``lower.kinds``).

Layer graphs are analytical specs, so producer/consumer shapes line up
only approximately (conv halos, flattening before FC, LSTM gate merges,
inception concat).  The walk closes the gap with one canonical
**adapter**:

  1. equal per-batch size        -> reshape (flatten before FC, 2-D<->4-D);
  2. channel-matched 4-D tensors -> centered zero-pad / crop of the
     spatial dims (reproduces e.g. AlexNet's conv padding exactly);
  3. divisible per-batch size    -> fold-sum over the leading groups
     (LSTM gate merge: 4*hidden -> hidden);

and multi-source eltwise layers whose channel counts partition the output
(inception concat) embed each source at its channel offset, so the n-ary
sum kernel computes the concatenation.

Token-major layers (a transformer's fc, norm, glu and eltwise layers over
[batch * seq, width]) need three more steps, each part of its layer:
an attention layer splits Q, K and V out of its ``qkv`` source
(``split_qkv``) and hands its output back token-major (``merge_heads``);
an fc with ``meta["last_position"]`` reads each sequence's last token
(``last_positions``).  A layer with ``meta["tied"]`` reads the weights
fed for the layer it names (``LayerSpec.weight_owner``), so a looped
stack's weights are fed once.

**The Pallas chain** realizes the plan's buffer schedule: forwarded
tensors (segment-internal, see ``netplan``) stay live jax arrays handed
from producer to consumers; boundary tensors are materialized to host
numpy after the producer and re-uploaded when consumed, the execution
analogue of a DRAM store and reload.
"""
from __future__ import annotations

import dataclasses
import math
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.backend import (DEFAULT_BACKEND, ORACLE_BACKEND,
                               backend_interprets, resolve_backend)
from ..obs import metrics, trace, watch
from ..workloads.layers import LayerSpec
from .exec import (ORACLE_KERNELS, ORACLE_TOL, _check_compiled_pallas,
                   pallas_kernels, rel_error)
from .kinds import KINDS, heads, required_input_shape
from .netplan import NetworkPlan
from .plan import KernelPlan


# ---------------------------------------------------------------------------
# shapes + the canonical adapter
# ---------------------------------------------------------------------------


def adapt_tensor(arr: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Adapt a producer output to a consumer's required input shape (see
    module docstring for the three rules)."""
    arr = jnp.asarray(arr)
    if tuple(arr.shape) == tuple(shape):
        return arr
    n = shape[0]
    src_per = int(np.prod(arr.shape[1:]))
    dst_per = int(np.prod(shape[1:]))
    if src_per == dst_per:
        return arr.reshape(shape)
    if arr.ndim == 4 and len(shape) == 4 and arr.shape[1] == shape[1]:
        out = arr
        for ax in (2, 3):
            d = shape[ax] - out.shape[ax]
            if d > 0:
                pad = [(0, 0)] * 4
                pad[ax] = (d // 2, d - d // 2)
                out = jnp.pad(out, pad)
            elif d < 0:
                lo = (-d) // 2
                out = jax.lax.slice_in_dim(out, lo, lo + shape[ax], axis=ax)
        return out
    if src_per % dst_per == 0:
        k = src_per // dst_per
        return arr.reshape((n, k, dst_per)).sum(axis=1).reshape(shape)
    raise ValueError(f"cannot adapt shape {tuple(arr.shape)} -> "
                     f"{tuple(shape)}")


def last_positions(arr: jnp.ndarray, seq: int) -> jnp.ndarray:
    """Each sequence's last position of a token-major [batch * seq, ...]
    tensor: [batch, ...]."""
    return arr.reshape((-1, seq) + tuple(arr.shape[1:]))[:, -1]


def layer_input(layer: LayerSpec, arr: jnp.ndarray) -> jnp.ndarray:
    """A producer's output as the layer's kernel consumes it: the last
    positions first where the layer asks for them, then the adapter."""
    if "last_position" in layer.meta:
        arr = last_positions(arr, int(layer.meta["last_position"]))
    return adapt_tensor(arr, required_input_shape(layer))


def split_qkv(layer: LayerSpec, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Q, K and V [batch * heads, seq, head_dim] out of a ``qkv`` output
    [batch * seq, (heads + 2 * kv_heads) * head_dim] (per token: the
    query heads, then the key heads, then the value heads); K/V heads
    repeat over their query heads."""
    h, kv = heads(layer)
    b, s, d = int(layer.meta["batch"]), layer.dim("X"), layer.dim("K")
    t = x.reshape(b, s, h + 2 * kv, d).transpose(0, 2, 1, 3)
    q, k, v = t[:, :h], t[:, h:h + kv], t[:, h + kv:]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
    return tuple(a.reshape(b * h, s, d) for a in (q, k, v))


def merge_heads(layer: LayerSpec, o: jnp.ndarray) -> jnp.ndarray:
    """[batch * heads, seq, head_dim] -> token-major [batch * seq,
    heads * head_dim]."""
    h, _ = heads(layer)
    b, s, d = int(layer.meta["batch"]), layer.dim("X"), layer.dim("K")
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(b * s,
                                                                h * d)


def _eltwise_operands(srcs: Sequence[jnp.ndarray],
                      layer: LayerSpec) -> List[jnp.ndarray]:
    """Adapt eltwise sources to the output shape.  When the sources'
    channel counts partition the output channels (inception concat), each
    source is embedded at its channel offset so the sum kernel computes
    the concatenation; otherwise every source adapts independently and
    the kernel computes a plain sum (residual add, gate merge)."""
    shape = required_input_shape(layer)
    C = shape[1]
    chans = [a.shape[1] if a.ndim == 4 else -1 for a in srcs]
    if len(srcs) > 1 and all(c > 0 for c in chans) and sum(chans) == C \
            and any(c != C for c in chans):
        out, off = [], 0
        for a, c in zip(srcs, chans):
            a4 = adapt_tensor(a, (shape[0], c, shape[2], shape[3]))
            out.append(jnp.pad(a4, ((0, 0), (off, C - off - c),
                                    (0, 0), (0, 0))))
            off += c
        return out
    return [adapt_tensor(a, shape) for a in srcs]


def layer_output(plan: KernelPlan, srcs: Sequence[jnp.ndarray],
                 feed: Optional[jnp.ndarray], w: Optional[jnp.ndarray],
                 kernels: Dict[str, Callable]) -> jnp.ndarray:
    """The graph walk's one step: a layer's output from its in-graph
    source values ``srcs`` (or, for a graph source, its ``.I`` ``feed``)
    and its weight ``w``, on ``kernels[plan.kind]``.  An n-ary sum's
    sources align through ``_eltwise_operands``.  Any other layer reads
    its first source through ``layer_input``, or its feed through the
    adapter; attention splits Q, K and V out of it and merges the heads
    of its output."""
    layer = plan.layer
    kind, kernel = KINDS[plan.kind], kernels[plan.kind]
    if kind.sums_sources:
        return kernel(plan, *_eltwise_operands(
            list(srcs) if srcs else [feed], layer))
    x = layer_input(layer, srcs[0]) if srcs else \
        adapt_tensor(feed, required_input_shape(layer))
    if kind.heads:
        return merge_heads(layer, kernel(plan, *split_qkv(layer, x)))
    return kernel(plan, x, w) if kind.weight else kernel(plan, x)


# ---------------------------------------------------------------------------
# deterministic network inputs (external activations + per-layer weights)
# ---------------------------------------------------------------------------

def _key(seed: int, name: str) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed),
                              zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_network_inputs(nplan: NetworkPlan,
                        seed: int = 0) -> Dict[str, jnp.ndarray]:
    """``"<layer>.I"`` external activations for graph sources and
    ``"<layer>.W"`` weights for the kinds that have them, each from the
    kind's initialiser (``kinds.LayerKind.weight``): variance-scaled so
    activations stay O(1) through deep graphs, and norm gains near 1.  A
    layer that reads another's weights (``meta["tied"]``) is fed none of
    its own."""
    inputs: Dict[str, jnp.ndarray] = {}
    for name in nplan.order:
        layer = nplan.plans[name].layer
        if not any(s in nplan.plans for s in layer.src):
            inputs[f"{name}.I"] = jax.random.normal(
                _key(seed, name + ".I"), required_input_shape(layer),
                jnp.float32)
        kind = KINDS.get(layer.kind)    # a kind no tier runs: no weights
        if kind and kind.weight and layer.weight_owner == name:
            inputs[f"{name}.W"] = kind.weight(_key(seed, name + ".W"),
                                              layer)
    return inputs


# ---------------------------------------------------------------------------
# per-layer steps + the execution chain
# ---------------------------------------------------------------------------

def _check_executable(nplan: NetworkPlan) -> None:
    bad = nplan.invalid_layers()
    if bad:
        raise ValueError(
            f"network plan {nplan.graph_name!r} is not executable: "
            + "; ".join(f"{n}: {r}" for n, r in bad))


def layer_steps(nplan: NetworkPlan, weights: Dict, backend: str
                ) -> Dict[str, Tuple[Callable, Tuple[str, ...]]]:
    """``{layer: (step, args)}``: each layer of the plan as one jitted
    step of the graph walk on the Pallas kernels of ``backend``
    (``interpret`` or ``pallas``), with its ``.W`` weight from
    ``weights`` captured.  ``step(*values)`` takes the values of
    ``args``: the layer's in-graph sources, or its ``.I`` feed.  Refuses
    a plan that cannot execute and, under ``pallas``, any plan the
    compiled Pallas guards (revisit order, TPU tiling) refuse, before
    anything compiles."""
    _check_executable(nplan)
    if backend == "pallas":
        for name in nplan.order:
            _check_compiled_pallas(nplan.plans[name])
    kernels = pallas_kernels(backend_interprets(backend))
    steps = {}
    for name in nplan.order:
        plan = nplan.plans[name]
        srcs = tuple(s for s in plan.layer.src if s in nplan.plans)
        w = weights.get(f"{plan.layer.weight_owner}.W")

        def step(*xs, plan=plan, fed=not srcs, w=w):
            return layer_output(plan, () if fed else xs,
                                xs[0] if fed else None, w, kernels)
        steps[name] = (jax.jit(step), srcs or (f"{name}.I",))
    return steps


@dataclasses.dataclass
class NetworkExecution:
    """Outputs of one end-to-end network run plus the realized buffer
    schedule (which tensors stayed on-chip vs round-tripped).  Under the
    fused ``compiled`` backend nothing crosses the host at all —
    ``roundtrips`` then lists the segment-*boundary* tensors (the plan's
    DRAM analogue), which stay device-resident inside the executable."""

    outputs: Dict[str, jnp.ndarray]
    forwarded: Tuple[str, ...]      # handed on-chip, never left the device
    roundtrips: Tuple[str, ...]     # materialized to host numpy
    seconds: float
    backend: str = "interpret"


def network_runner(nplan: NetworkPlan, inputs: Dict,
                   backend: str = ORACLE_BACKEND,
                   keep: str = "all") -> Callable[[], NetworkExecution]:
    """Build a reusable ``() -> NetworkExecution`` for the plan.

    ``backend`` selects the execution tier (``kernels.backend``):

      * ``"interpret"`` — per-layer interpret-mode ``pl.pallas_call``
        chain, the bit-accuracy oracle.  Forwarded tensors pass between
        kernels as live jax arrays; boundary tensors are materialized to
        host numpy and re-uploaded at the consumer — the host round-trip
        that models the DRAM boundary.  Each layer step (adapter +
        kernel) is staged once and re-invocations reuse the compiled
        executables (``layer_steps``).
      * ``"pallas"`` — the same chain with compiled Pallas kernels (TPU).
      * ``"compiled"`` — fused segments (``fuse.fused_runner``): the
        whole plan runs as one jitted executable from the process-wide
        executable cache; ``keep="boundary"`` returns only segment-
        boundary outputs (the serving/measurement path), ``keep="outputs"``
        only the graph's sink outputs, ``keep="all"`` every layer output
        (verification).
    """
    backend = resolve_backend(backend)
    if backend == "compiled":
        from .fuse import fused_runner
        fused = fused_runner(nplan)
        fwd = nplan.forwarded()
        boundary = tuple(n for n in nplan.order if n not in fwd)

        def run_fused() -> NetworkExecution:
            with trace.span("netexec.run"):
                t0 = time.perf_counter()
                outputs = fused(inputs, keep=keep)
                with trace.span("netexec.wait"):
                    for v in outputs.values():
                        jax.block_until_ready(v)
                return NetworkExecution(
                    outputs=outputs, forwarded=fwd, roundtrips=boundary,
                    seconds=time.perf_counter() - t0, backend=backend)
        return run_fused

    steps = layer_steps(nplan, inputs, backend)

    def run() -> NetworkExecution:
        t0 = time.perf_counter()
        onchip: Dict[str, jnp.ndarray] = {}
        host: Dict[str, np.ndarray] = {}
        for name in nplan.order:
            fn, args = steps[name]
            out = fn(*(onchip[s] if s in onchip else jnp.asarray(
                host[s] if s in host else inputs[s]) for s in args))
            if nplan.placements[name].forwarded:
                onchip[name] = out              # stays a live device array
            else:
                host[name] = np.asarray(out)    # the host round-trip
        for v in onchip.values():
            jax.block_until_ready(v)
        seconds = time.perf_counter() - t0
        outputs = {**onchip,
                   **{k: jnp.asarray(v) for k, v in host.items()}}
        return NetworkExecution(outputs=outputs, forwarded=tuple(onchip),
                                roundtrips=tuple(host), seconds=seconds,
                                backend=backend)
    return run


def execute_network(nplan: NetworkPlan, inputs: Optional[Dict] = None,
                    seed: int = 0,
                    backend: str = ORACLE_BACKEND) -> NetworkExecution:
    """Run every kernel of the plan in topological order (one-shot
    convenience over ``network_runner``)."""
    inputs = inputs if inputs is not None else make_network_inputs(nplan,
                                                                   seed)
    return network_runner(nplan, inputs, backend=backend)()


# ---------------------------------------------------------------------------
# whole-graph reference forward pass + verification
# ---------------------------------------------------------------------------

def reference_network(nplan: NetworkPlan,
                      inputs: Dict) -> Dict[str, jnp.ndarray]:
    """Ground truth: the graph walk on the ``kernels/ref.py`` oracles
    (``exec.ORACLE_KERNELS``), in the same order."""
    vals: Dict[str, jnp.ndarray] = {}
    for name in nplan.order:
        plan = nplan.plans[name]
        layer = plan.layer
        vals[name] = layer_output(
            plan, [vals[s] for s in layer.src if s in vals],
            inputs.get(f"{name}.I"), inputs.get(f"{layer.weight_owner}.W"),
            ORACLE_KERNELS)
    return vals


@dataclasses.dataclass
class NetworkVerification:
    ok: bool
    max_rel_err: float
    worst_layer: str
    errors: Dict[str, float]
    n_forwarded: int


def compare_network(nplan: NetworkPlan, ex: NetworkExecution,
                    inputs: Dict,
                    tol: float = ORACLE_TOL) -> NetworkVerification:
    """Compare **every** layer output of an execution against the
    whole-graph reference pass (per-layer max relative error) — the one
    comparison rule shared by ``verify_network``, the calibration sweep
    and callers reusing a ``network_runner``."""
    want = reference_network(nplan, inputs)
    errors = {n: rel_error(ex.outputs[n], want[n]) for n in nplan.order}
    worst = max(errors, key=errors.get)
    return NetworkVerification(
        ok=errors[worst] < tol, max_rel_err=errors[worst],
        worst_layer=worst, errors=errors, n_forwarded=len(ex.forwarded))


def verify_network(nplan: NetworkPlan, backend: str = ORACLE_BACKEND,
                   seed: int = 0,
                   tol: float = ORACLE_TOL) -> NetworkVerification:
    """Execute the plan and compare against the whole-graph reference
    (one-shot convenience over ``compare_network``).  The default backend
    is the interpret oracle; pass ``backend="compiled"`` to verify the
    fused tier (it always keeps every layer output for the comparison)."""
    inputs = make_network_inputs(nplan, seed)
    ex = execute_network(nplan, inputs, backend=backend)
    return compare_network(nplan, ex, inputs, tol)


_m_drift = metrics.histogram(
    "latency_drift_ratio",
    "measured / predicted network latency of lowered plans",
    ("source", "backend"), buckets=metrics.DRIFT_BUCKETS)


def record_latency_drift(predicted_seconds: Optional[float],
                         measured_seconds: float,
                         source: str = "netexec",
                         backend: str = "interpret") -> Optional[float]:
    """Record one predicted-vs-measured latency pair into the
    ``latency_drift_ratio`` histogram (+ a trace instant), so cost-model
    calibration decay is visible at serve time, not only in the
    calibration bench.  The ``backend`` label keeps interpreter-tax
    ratios from polluting the compiled tier's drift signal.  Returns the
    ratio, or None if either side is unusable (zero/negative prediction,
    NaN measurement)."""
    if not predicted_seconds or predicted_seconds <= 0.0:
        return None
    if not math.isfinite(measured_seconds) or measured_seconds <= 0.0:
        return None
    ratio = measured_seconds / predicted_seconds
    _m_drift.observe(ratio, source=source, backend=backend)
    watch.note_sample(predicted_seconds, measured_seconds,
                      source=source, backend=backend)
    trace.instant("netexec.latency_drift", source=source, backend=backend,
                  ratio=round(ratio, 4))
    return ratio


def measure_network(nplan: NetworkPlan, inputs: Optional[Dict] = None,
                    iters: int = 2, warmup: int = 1,
                    runner: Optional[Callable[[], NetworkExecution]] = None,
                    predicted_seconds: Optional[float] = None,
                    drift_source: str = "netexec",
                    backend: str = DEFAULT_BACKEND) -> float:
    """Measured wall-clock seconds for one end-to-end network execution
    (min over ``iters`` after ``warmup`` runs compile every layer step).
    Includes the buffer schedule's real host round-trips — network time,
    not a sum of isolated kernel times.  Measurement defaults to the
    **compiled** tier (the serving path: one fused executable per
    segment, boundary outputs only, forwarded tensors never
    materialize); pass ``backend="interpret"`` to time the oracle
    instead.

    Pass an existing ``network_runner`` (with ``warmup=0`` if it already
    ran, e.g. for verification) to reuse its compiled steps — the single
    timing protocol behind the calibration sweep and the quickstart."""
    backend = resolve_backend(backend)
    if runner is None:
        inputs = inputs if inputs is not None \
            else make_network_inputs(nplan)
        runner = network_runner(
            nplan, inputs, backend=backend,
            keep="boundary" if backend == "compiled" else "all")
        warmup = max(1, warmup)         # fresh steps always need a compile
    for _ in range(warmup):
        runner()
    out = min(runner().seconds for _ in range(max(1, iters)))
    if predicted_seconds is not None:
        record_latency_drift(predicted_seconds, out, source=drift_source,
                             backend=backend)
    return out
