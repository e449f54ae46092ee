"""The compiled tier: a ``NetworkPlan``'s segments, or the whole net,
traced into single jitted executables.

  * **one jitted function per chain segment** — the graph walk
    (``netexec.layer_output``) over every layer of the segment, on the
    compiled kernels (``compiled_kernels``), adapter traced inline,
    inside a single ``jax.jit`` scope.  Forwarded tensors
    (``LayerScheme.forward_bytes``, the on-chip forwarding machinery)
    are live values inside one executable, not jax arrays round-tripping
    through Python dispatch;
  * **a whole-``NetworkPlan`` jitted entry point** — the segment
    functions chained into one executable, external activations donatable
    (weights never donated: they are the resident state a serving node
    reuses across requests);
  * **a process-wide executable cache** keyed by the plan *signature*
    (shapes + kinds + blocking + buffer schedule — everything that
    determines the traced computation), so repeated executions of the
    same plan — autotune top-k re-ranking, ``SolveServer`` measured
    re-ranking, mesh task replay — pay tracing/compilation exactly once.

Each compiled kernel (``_fc``, ``_conv``, ``_pool``, ``_eltwise``,
``_attention``, ``_norm``, ``_glu``) computes its layer's math in plain
XLA, independent of the ``kernels/ref.py`` oracles it is verified
against, and is looked up by name on this module each time a layer
traces, so a kernel swapped on the module changes the executable.  A
conv is one ``lax.conv_general_dilated`` at HIGHEST: XLA's windowed
convolution folds the R/S taps into the contraction and writes the
output once.  The pool keeps the R/S window as a slice + max loop.  The
Pallas twins in ``exec.py`` keep the per-tap loop in-block.

What the compiled tier does *not* replay is the solver's DRAM-level
grid walk: XLA owns the loop schedule inside a fused segment — the
solver's inter-layer decisions (segmentation, forwarding) shape the
executable, the intra-layer nest is the cost model's concern and stays
measurable on the Pallas tier.
"""
from __future__ import annotations

import hashlib
import json
import re
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs import metrics, trace
from .exec import attention_inputs, plan_fn
from .kinds import WEIGHT_KINDS
from .netexec import _check_executable, layer_output, make_network_inputs
from .netplan import NetworkPlan
from .plan import KernelPlan

# -- telemetry (repro.obs) ---------------------------------------------------
_m_cache = metrics.counter(
    "fused_cache_events_total",
    "fused-executable cache events (hit / miss / eviction)", ("event",))
_m_size = metrics.gauge("fused_cache_size",
                        "fused executables resident in the process cache")
_m_compile = metrics.histogram(
    "fused_compile_seconds",
    "wall clock of a fused-executable call that traced: trace, compile "
    "(or persistent-cache load) and one execution")
_m_weights = metrics.gauge(
    "fused_weight_arrays",
    "weight arrays a call of the last-built fused executable is fed")
_m_tied = metrics.gauge(
    "fused_tied_layers",
    "layers of the last-built fused executable that read another "
    "layer's weights")
_m_pairs = metrics.gauge(
    "fused_attention_pair_share",
    "score pairs the causal attention layers of the last-built fused "
    "executable form, over the pairs of their whole score matrices")


# ---------------------------------------------------------------------------
# compiled per-layer kernels (pure jnp, traced into the segment executable)
# ---------------------------------------------------------------------------

#: matmul precision of every compiled-tier product.  f32 operands at
#: HIGHEST, like the ``kernels/ref.py`` oracles: on the TPU the default
#: precision is a single bf16 pass, which would make the chip compute
#: something other than what the CPU tests check (``exec.ORACLE_TOL``).
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def _fc(plan: KernelPlan, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(x, w, precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)


def _conv(plan: KernelPlan, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """XLA's windowed convolution over the adapted input: ``x`` is
    exactly ``input_extent(layer)`` = ``(X-1)*stride + R`` wide, so a
    VALID window walk yields ``[N, K, X, Y]`` and the R x S taps fold
    into the contraction instead of a pass over the output each.

    The conv is stated in NHWC/HWIO with the NCHW/OIHW tensors
    transposed around it.  The TPU's layout assignment absorbs the
    transposes (the program matches an NCHW statement's but for the
    input pad of a padded conv), and XLA's CPU backend runs this form as
    it is, where it rebuilds an NCHW conv without the layer's
    ``op_name`` and ``FusedNetwork.op_layers`` would lose the conv."""
    layer = plan.layer
    stride = int(layer.meta["stride"])
    out = jax.lax.conv_general_dilated(
        x.transpose(0, 2, 3, 1), w.transpose(2, 3, 1, 0),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32).transpose(0, 3, 1, 2)
    want = (x.shape[0], layer.dim("K"), layer.dim("X"), layer.dim("Y"))
    if out.shape != want:
        raise ValueError(f"conv {layer.name!r}: input {x.shape} gives "
                         f"{out.shape}, the layer wants {want}")
    return out


def _pool(plan: KernelPlan, x: jnp.ndarray) -> jnp.ndarray:
    layer = plan.layer
    R, S = int(layer.meta["R"]), int(layer.meta["S"])
    stride = int(layer.meta["stride"])
    N, C = x.shape[0], x.shape[1]
    XO, YO = layer.dim("X"), layer.dim("Y")
    acc = jnp.full((N, C, XO, YO), -jnp.inf, jnp.float32)
    for r in range(R):
        for s in range(S):
            patch = jax.lax.slice(
                x, (0, 0, r, s),
                (N, C, r + (XO - 1) * stride + 1,
                 s + (YO - 1) * stride + 1),
                (1, 1, stride, stride))
            acc = jnp.maximum(acc, patch)
    return acc


def _eltwise(plan: KernelPlan, xs) -> jnp.ndarray:
    acc = xs[0].astype(jnp.float32)
    for x in xs[1:]:
        acc = acc + x
    return acc


#: fewest query rows a block of a split causal attention layer takes
#: (``causal_blocks``)
CAUSAL_QUERY_BLOCK = 512
#: most blocks a causal attention layer is split into.  Each block's
#: products and softmax compile to fusions of their own: one layer at
#: 4096 tokens compiles for a v5e to 2.0 MB of executable unsplit, 6.6 MB
#: in 4 blocks and 12.1 MB in 8, and a net's whole executable has to stay
#: small enough for JAX's persistent compilation cache to keep it
CAUSAL_MAX_BLOCKS = 4


def causal_blocks(sq: int, skv: int) -> List[Tuple[int, int, int]]:
    """``[(q0, q1, kend)]``: the query blocks of a causal attention layer
    of ``sq`` queries over ``skv`` keys, where query ``i`` sees the keys
    up to ``i + skv - sq``.  Block ``[q0, q1)`` forms scores against
    keys ``[0, kend)``, the prefix its last row sees, and no others.
    Blocks are ``CAUSAL_QUERY_BLOCK`` rows or more, a multiple of the
    MXU's 128-row tile, and at most ``CAUSAL_MAX_BLOCKS`` of them.  A
    layer shorter than two blocks, or with fewer keys than queries, is
    one block over every key."""
    bq = max(CAUSAL_QUERY_BLOCK,
             128 * -(-sq // (128 * CAUSAL_MAX_BLOCKS)))
    if sq < 2 * CAUSAL_QUERY_BLOCK or skv < sq:
        return [(0, sq, skv)]
    return [(q0, min(q0 + bq, sq), min(q0 + bq, sq) + skv - sq)
            for q0 in range(0, sq, bq)]


def attention_pair_share(layers) -> float:
    """The score pairs (query x key) the causal attention layers among
    ``layers`` form under ``causal_blocks``, over the pairs of their
    whole score matrices; 1.0 when there is none."""
    formed = whole = 0
    for layer in layers:
        if layer.kind != "attention" or not layer.meta.get("causal"):
            continue
        sq, skv, n = layer.dim("X"), layer.dim("C"), layer.dim("N")
        formed += n * sum((q1 - q0) * kend
                          for q0, q1, kend in causal_blocks(sq, skv))
        whole += n * sq * skv
    return formed / whole if whole else 1.0


def _attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            offset: Optional[int]) -> jnp.ndarray:
    """softmax(q k^T / sqrt(D)) v over [N, S, D]; with an ``offset``,
    key ``j`` is masked from query row ``i`` unless ``j <= i + offset``."""
    s = jnp.einsum("nqd,nkd->nqk", q, k, precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    if offset is not None:
        qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
        s = jnp.where(kpos <= qpos + offset, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nqk,nkd->nqd", p, v, precision=MATMUL_PRECISION,
                      preferred_element_type=jnp.float32)


def _attention(plan: KernelPlan, q: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray) -> jnp.ndarray:
    """softmax(q k^T / sqrt(D)) v over [N, S, D], with q and k rotated by
    RoPE and key positions after the query's masked, as the layer's meta
    says.  A causal layer runs its queries in the blocks of
    ``causal_blocks``: each block forms scores against its key prefix
    alone, never the keys past its last row, which the mask would zero.
    Every row still holds all of its keys, so each block's softmax is
    the exact row softmax; the blocks' outputs are concatenated along
    the query axis."""
    layer = plan.layer
    q, k = attention_inputs(layer, q, k)
    sq, skv = q.shape[1], k.shape[1]
    if not layer.meta.get("causal"):
        return _attend(q, k, v, None)
    return jnp.concatenate(
        [_attend(q[:, q0:q1], k[:, :kend], v[:, :kend], q0 + skv - sq)
         for q0, q1, kend in causal_blocks(sq, skv)], axis=1)


def _norm(plan: KernelPlan, x: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + float(plan.layer.meta["eps"])) * g


def _glu(plan: KernelPlan, x: jnp.ndarray) -> jnp.ndarray:
    c = plan.layer.dim("C")
    gate, up = x[:, :c], x[:, c:]
    return gate * jax.nn.sigmoid(gate) * up


def compiled_kernels() -> Dict[str, Callable]:
    """kind -> compiled kernel ``kernel(plan, *operands)``, read off the
    module when called: the graph walk calls this as each layer traces,
    so a kernel replaced on the module is the one traced."""
    return {"conv": _conv, "fc": _fc, "attention": _attention,
            "pool": _pool, "eltwise": lambda plan, *xs: _eltwise(plan, xs),
            "norm": _norm, "glu": _glu}


def compiled_plan_fn(plan: KernelPlan) -> Tuple[Callable, Tuple[str, ...]]:
    """(fn, input names) — the layer-tier compiled kernel for one plan,
    used by ``exec.plan_runner(backend="compiled")`` and the per-backend
    calibration sweep.  Unlike compiled Pallas, any DRAM loop order is
    executable (XLA owns the schedule), so no revisit-order guard."""
    return plan_fn(plan, "compiled")


# ---------------------------------------------------------------------------
# the plan signature: cache key over everything that shapes the executable
# ---------------------------------------------------------------------------

def plan_signature(nplan: NetworkPlan) -> str:
    """Content hash of the traced computation: layer shapes/kinds/meta,
    graph wiring, segment slicing and the buffer schedule.  Two plans
    with equal signatures trace to identical executables, so re-lowering
    the same schedule (autotune iterations, store-served re-executions,
    mesh replays) hits the process cache instead of re-tracing."""
    doc: Dict = {"graph": nplan.graph_name, "layers": [], "segments": []}
    for name in nplan.order:
        plan = nplan.plans[name]
        layer = plan.layer
        doc["layers"].append({
            "name": name,
            "kind": plan.kind,
            "dims": sorted((d, int(v)) for d, v in layer.dims.items()),
            "meta": sorted((k, repr(v)) for k, v in layer.meta.items()),
            "src": [s for s in layer.src if s in nplan.plans],
            "block": sorted((d, int(v)) for d, v in plan.block.items()),
            "grid": [(ax.dim, ax.steps) for ax in plan.grid],
            "forwarded": nplan.placements[name].forwarded,
        })
    for seg in nplan.segments:
        doc["segments"].append([seg.start, seg.stop,
                                round(seg.granule_frac, 12)])
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def input_specs(nplan: NetworkPlan) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract shapes of the plan's external feed (mirrors
    ``make_network_inputs``, with no array made) — what the fused
    executable is traced for."""
    return jax.eval_shape(lambda: make_network_inputs(nplan, seed=0))


# ---------------------------------------------------------------------------
# segment + network function builders
# ---------------------------------------------------------------------------

def _layer_out(nplan: NetworkPlan, name: str, vals: Dict,
               feed: Dict) -> jnp.ndarray:
    """One layer's output during tracing: the graph walk over sources
    from already-computed ``vals`` (in-graph), falling back to the
    external ``feed`` (the ``.I`` inputs — and, at segment granularity,
    boundary tensors from earlier segments).  Everything it traces,
    adapter included, sits under ``jax.named_scope(name)``: the layer's
    name reaches each compiled instruction's ``op_name``
    (``FusedNetwork.op_layers``)."""
    plan = nplan.plans[name]
    layer = plan.layer
    srcs = [vals[s] if s in vals else feed[s]
            for s in layer.src if s in nplan.plans]
    with jax.named_scope(name):
        return layer_output(plan, srcs, feed.get(f"{name}.I"),
                            feed.get(f"{layer.weight_owner}.W"),
                            compiled_kernels())


_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def hlo_op_layers(hlo_text: str, layers) -> Dict[str, str]:
    """``{instruction: layer}`` over the ENTRY computation of an HLO
    module's text: each instruction whose ``op_name`` metadata carries a
    ``jax.named_scope`` of one of ``layers`` (``jit(fn)/pool1/max`` ->
    ``pool1``).  Instructions outside every layer scope (parameters and
    their copies, ``copy-start``/``copy-done``) are left out."""
    names = set(layers)
    out: Dict[str, str] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if not in_entry:
            in_entry = line.startswith("ENTRY ")
            continue
        if line.startswith("}"):
            break
        instr, op = _HLO_INSTR.match(line), _HLO_OP_NAME.search(line)
        if instr is None or op is None:
            continue
        layer = next((p for p in op.group(1).split("/")[1:] if p in names),
                     None)
        if layer is not None:
            out[instr.group(1)] = layer
    return out


def _segment_io(nplan: NetworkPlan, seg) -> Tuple[Tuple[str, ...],
                                                  Tuple[str, ...]]:
    """(consumes, produces) boundary names of one segment: tensors read
    from outside the segment (boundary tensors, external ``.I`` feeds and
    ``.W`` weights) and tensors any later consumer — or the network
    output — needs."""
    inseg = set(seg.layer_names)
    consumes: List[str] = []
    for n in seg.layer_names:
        layer = nplan.plans[n].layer
        srcs = [s for s in layer.src if s in nplan.plans]
        if srcs:
            consumes += [s for s in srcs if s not in inseg]
        else:
            consumes.append(f"{n}.I")
        if layer.kind in WEIGHT_KINDS:
            consumes.append(f"{layer.weight_owner}.W")
    produces = []
    for n in seg.layer_names:
        cons = nplan.placements[n].consumers
        if not cons or any(c not in inseg for c in cons):
            produces.append(n)
    return tuple(dict.fromkeys(consumes)), tuple(produces)


#: what a whole-net call returns: every layer's output, the segment
#: boundaries and network outputs, or the graph's sink outputs alone
KEEPS = ("all", "boundary", "outputs")


class FusedNetwork:
    """The compiled tier of one ``NetworkPlan``: lazily-built jitted
    executables at two granularities (whole net, single segment), every
    variant cached on this object — which the process-wide cache in turn
    keys by plan signature, so tracing happens once per plan content.

    ``traces`` counts actual jax retraces (a Python side effect at trace
    time): the zero-retrace guarantee the executable cache is tested on.

    Each call is traced by ``obs.trace``: ``fuse.feed`` splits the feed,
    ``fuse.compile`` covers the first call of a jitted variant (trace,
    compile or cache load, one run) and ``fuse.dispatch`` every later
    call, until the jitted call returns.

    Building one sets the gauges ``fused_weight_arrays`` (the ``.W``
    arrays a call is fed: one per weight owner), ``fused_tied_layers``
    (the layers that read another layer's weights) and
    ``fused_attention_pair_share`` (``attention_pair_share``).
    """

    def __init__(self, nplan: NetworkPlan):
        _check_executable(nplan)             # errors name the layer
        self.nplan = nplan
        self.signature = plan_signature(nplan)
        self.traces = 0
        self._fns: Dict[Tuple, Callable] = {}
        self._called: set = set()            # variants run at least once
        self._lock = threading.Lock()
        self.segment_io = [_segment_io(nplan, seg)
                           for seg in nplan.segments]
        layers = [nplan.plans[n].layer for n in nplan.order]
        self.weight_arrays = len({l.weight_owner for l in layers
                                  if l.kind in WEIGHT_KINDS})
        self.tied_layers = sum(l.weight_owner != l.name for l in layers
                               if l.kind in WEIGHT_KINDS)
        self.attention_pair_share = attention_pair_share(layers)
        _m_weights.set(self.weight_arrays)
        _m_tied.set(self.tied_layers)
        _m_pairs.set(self.attention_pair_share)

    # -- builders -----------------------------------------------------------

    def _trace_marker(self) -> None:
        self.traces += 1                     # runs at trace time only

    def _build_network(self, keep: str, donate: bool) -> Callable:
        nplan = self.nplan
        if keep == "all":
            kept = list(nplan.order)
        elif keep == "outputs":              # the graph's sinks only
            kept = [n for n in nplan.order
                    if not nplan.placements[n].consumers]
        else:                                # "boundary": serving outputs
            kept = [n for s in self.segment_io for n in s[1]]

        def fn(acts: Dict, weights: Dict) -> Dict:
            self._trace_marker()
            feed = {**acts, **weights}
            vals: Dict[str, jnp.ndarray] = {}
            for seg in nplan.segments:       # segments chained in order:
                for n in seg.layer_names:    # forwarded AND boundary
                    vals[n] = _layer_out(nplan, n, vals, feed)  # tensors
            return {n: vals[n] for n in kept}    # stay traced values

        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    def _build_segment(self, index: int) -> Callable:
        nplan = self.nplan
        seg = nplan.segments[index]

        def fn(state: Dict) -> Dict:
            self._trace_marker()
            vals: Dict[str, jnp.ndarray] = {}
            for n in seg.layer_names:
                vals[n] = _layer_out(nplan, n, vals, state)
            return {n: vals[n] for n in self.segment_io[index][1]}

        return jax.jit(fn)

    def _fn(self, key: Tuple) -> Callable:
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = (self._build_segment(key[1]) if key[0] == "seg"
                      else self._build_network(key[1], key[2]))
                self._fns[key] = fn
        return fn

    def _timed(self, key: Tuple, *args):
        """Invoke a jitted variant under its host span (``fuse.compile``
        on the variant's first call, ``fuse.dispatch`` after); when the
        call traced, record its wall clock in ``fused_compile_seconds``."""
        fn = self._fn(key)
        with self._lock:
            first = key not in self._called
            self._called.add(key)
        span = (trace.span("fuse.compile", net=self.nplan.graph_name,
                           signature=self.signature[:12])
                if first else trace.span("fuse.dispatch"))
        before = self.traces
        t0 = time.perf_counter()
        with span:
            out = fn(*args)
        if self.traces > before:
            _m_compile.observe(time.perf_counter() - t0)
        return out

    def compiled_text(self, keep: str = "boundary") -> str:
        """The compiled module's text of the whole-net executable (the
        ``keep`` variant, not donating).  Lowers and compiles the variant
        (a cache load when it was compiled before); a set-up-time call,
        never on the serving path."""
        specs = input_specs(self.nplan)
        acts = {k: v for k, v in specs.items() if not k.endswith(".W")}
        weights = {k: v for k, v in specs.items() if k.endswith(".W")}
        return self._fn(("net", keep, False)).lower(
            acts, weights).compile().as_text()

    def op_layers(self, keep: str = "boundary") -> Dict[str, str]:
        """``{HLO instruction: layer}`` of the whole-net executable, read
        from its compiled text (``compiled_text``): the join key between
        a device trace's ``XLA Ops`` events, which are named by
        instruction, and the plan's layers."""
        return hlo_op_layers(self.compiled_text(keep), self.nplan.order)

    # -- execution ----------------------------------------------------------

    def __call__(self, inputs: Dict, keep: str = "all",
                 donate: bool = False) -> Dict[str, jnp.ndarray]:
        """Run the whole plan as one executable.  ``keep="all"`` returns
        every layer output (verification); ``keep="boundary"`` returns
        only segment-boundary/network outputs (the serving path —
        forwarded tensors never materialize); ``keep="outputs"`` returns
        the graph's sinks alone (a served prefill's logits, where the
        boundary tensors would not fit the device).  ``donate=True``
        donates the external activation buffers (weights are never
        donated); donated inputs must not be reused by the caller."""
        if keep not in KEEPS:
            raise ValueError(f"keep must be one of {KEEPS}, got {keep!r}")
        with trace.span("fuse.feed"):
            acts = {k: v for k, v in inputs.items()
                    if not k.endswith(".W")}
            weights = {k: v for k, v in inputs.items() if k.endswith(".W")}
        return self._timed(("net", keep, donate), acts, weights)

    def run_segment(self, index: int, state: Dict) -> Dict:
        """Run one fused segment executable over a boundary-state dict
        (must hold the segment's ``consumes`` names) — the mesh executor's
        per-task unit."""
        return self._timed(("seg", index), state)


# ---------------------------------------------------------------------------
# the process-wide executable cache
# ---------------------------------------------------------------------------

_CACHE: "OrderedDict[str, FusedNetwork]" = OrderedDict()
_CACHE_CAP = 32
_CACHE_LOCK = threading.Lock()
_cache_counts = {"hits": 0, "misses": 0, "evictions": 0}


def fused_runner(nplan: NetworkPlan, cache: bool = True) -> FusedNetwork:
    """The compiled tier's entry point: the ``FusedNetwork`` for this
    plan, served from the process-wide executable cache when an
    equal-signature plan was fused before (zero retrace on hit)."""
    if not cache:
        return FusedNetwork(nplan)
    sig = plan_signature(nplan)
    with _CACHE_LOCK:
        hit = _CACHE.get(sig)
        if hit is not None:
            _CACHE.move_to_end(sig)
            _cache_counts["hits"] += 1
            _m_cache.inc(event="hit")
            return hit
    # build outside the lock (tracing may be slow); losing a build race
    # just wastes one construction, never corrupts the cache
    fused = FusedNetwork(nplan)
    with _CACHE_LOCK:
        if sig in _CACHE:
            _CACHE.move_to_end(sig)
            return _CACHE[sig]
        _cache_counts["misses"] += 1
        _m_cache.inc(event="miss")
        _CACHE[sig] = fused
        while len(_CACHE) > _CACHE_CAP:
            _CACHE.popitem(last=False)
            _cache_counts["evictions"] += 1
            _m_cache.inc(event="eviction")
        _m_size.set(len(_CACHE))
    return fused


def cache_stats() -> Dict[str, int]:
    with _CACHE_LOCK:
        return {"size": len(_CACHE), **_cache_counts}


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()
        for k in _cache_counts:
            _cache_counts[k] = 0
        _m_size.set(0)


__all__ = ["FusedNetwork", "fused_runner", "plan_signature", "input_specs",
           "compiled_kernels", "compiled_plan_fn", "causal_blocks",
           "attention_pair_share", "hlo_op_layers", "cache_stats",
           "clear_cache"]
