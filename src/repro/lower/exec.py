"""The Pallas tier and the oracle of one ``KernelPlan``.

One generic Pallas kernel per layer kind (``_run_*``: matmul/fc, conv,
attention, pool, eltwise, RMSNorm, SwiGLU product), parameterized
entirely by the plan: the grid is the solver's DRAM-level loop nest
(same order), the BlockSpecs carry the plan's block sizes and index
maps, and reduction grid axes accumulate into the output block across
revisits (initialized on the first visit, exactly like the directive
model's partial-sum residency).

Each tier is a map from kind to kernel, every kernel called
``kernel(plan, *operands)`` (``lower.kinds``): ``pallas_kernels`` binds
the Pallas kernels' ``interpret`` flag from the backend,
``ORACLE_KERNELS`` holds the ``kernels/ref.py`` oracles, and
``fuse.compiled_kernels`` the compiled tier.  ``plan_runner`` runs one
plan on the kernel of its backend; ``make_inputs`` draws the plan's
operands from the kind table; ``reference_output`` is the oracle's
answer every backend is held to (``ORACLE_TOL``).

The ``interpret`` backend is the numerics oracle.  The ``pallas``
backend refuses, before compiling, any plan whose blocks break the TPU's
(8, 128) tiling rule (``_check_tpu_tiling``), which today is every plan
of an Eyeriss-sized template, whose blocks are cut for a small global
buffer.

Notes on fidelity:
  * everything on-chip (all node GBUFs + the PE arrays below them) is one
    Pallas block — a single-core Pallas program models the off-chip
    boundary, which is the boundary the solver's DRAM loop nest governs;
  * conv input halos: Pallas blocks cannot overlap, so the input streams
    in blocked over N/C with the full spatial extent and the kernel slices
    the (ix, iy) window dynamically — traffic is modeled pessimistically
    by the solver's halo multiplier either way;
  * attention keeps running (max, sum) softmax statistics in auxiliary
    *output* buffers indexed like O, so any loop order the solver picks —
    even with the KV-position axis outside the query axis — stays
    numerically exact across block revisits.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..kernels import ref
from ..kernels.backend import (ORACLE_BACKEND, backend_interprets,
                               resolve_backend)
from .kinds import input_extent, kind_of, operand_names
from .plan import KernelPlan


def _grid(plan: KernelPlan) -> Tuple[int, ...]:
    return plan.grid_shape if plan.grid else (1,)


def _check_compiled_revisit_order(plan: KernelPlan) -> None:
    """Compiled (non-interpret) Pallas requires revisits of an output block
    to be consecutive in grid order: every axis *inner* to an
    output-irrelevant (reduction) axis must itself be output-irrelevant.
    Interpret mode is buffer-backed and tolerates any order; compiled mode
    would silently accumulate into a flushed block, so refuse loudly."""
    rel = plan.layer.tensors["O"]
    seen_irrelevant = False
    for ax in plan.grid:
        if ax.dim not in rel:
            seen_irrelevant = True
        elif seen_irrelevant:
            raise ValueError(
                "compiled execution needs reduction grid axes innermost; "
                f"grid is ({', '.join(a.dim for a in plan.grid)}) — run in "
                "interpret mode or reorder the scheme's DRAM loop order")


#: TPU tiling of a 32-bit block's last two dims (sublanes, lanes)
TPU_TILE = (8, 128)


def _pallas_blocks(plan: KernelPlan):
    """(operand, block shape, array shape) of every BlockSpec the
    plan's ``pallas_call`` declares — mirrors the ``_run_*`` kernels."""
    layer = plan.layer
    b, d = plan.block, layer.dim
    if plan.kind == "fc":
        return [("I", (b["N"], b["C"]), (d("N"), d("C"))),
                ("W", (b["C"], b["K"]), (d("C"), d("K"))),
                ("O", (b["N"], b["K"]), (d("N"), d("K")))]
    if plan.kind in ("conv", "pool"):
        XI, YI = input_extent(layer)
        ch = "K" if plan.kind == "conv" else "C"
        out = [("I", (b["N"], b["C"], XI, YI), (d("N"), d("C"), XI, YI)),
               ("O", (b["N"], b[ch], b["X"], b["Y"]),
                (d("N"), d(ch), d("X"), d("Y")))]
        if plan.kind == "conv":
            R, S = int(layer.meta["R"]), int(layer.meta["S"])
            out.insert(1, ("W", (b["K"], b["C"], R, S),
                           (d("K"), d("C"), R, S)))
        return out
    if plan.kind == "eltwise":
        dims = ("N", "C", "X", "Y")
        return [("O", tuple(b[x] for x in dims), tuple(d(x) for x in dims))]
    if plan.kind == "attention":
        NH, Sq, Skv, D = d("N"), d("X"), d("C"), d("K")
        return [("Q", (b["N"], b["X"], D), (NH, Sq, D)),
                ("K", (b["N"], b["C"], D), (NH, Skv, D)),
                ("O", (b["N"], b["X"], D), (NH, Sq, D)),
                ("stats", (b["N"], b["X"]), (NH, Sq))]
    if plan.kind in ("norm", "glu"):
        return [("O", (b["N"], b["C"]), (d("N"), d("C")))]
    raise ValueError(f"unsupported kind {plan.kind!r}")


def _check_tpu_tiling(plan: KernelPlan) -> None:
    """Compiled Pallas on the TPU needs each block's last two dims
    divisible by ``TPU_TILE`` or equal to the array's; the solver sizes
    blocks for the template's on-chip buffer, not for that tiling.
    Refuse before compiling, naming the layer and the block."""
    for operand, block, shape in _pallas_blocks(plan):
        for bdim, adim, tile in zip(block[-2:], shape[-2:], TPU_TILE):
            if bdim != adim and bdim % tile:
                raise ValueError(
                    f"layer {plan.layer.name!r}: {operand} block "
                    f"{tuple(block)} of array {tuple(shape)} breaks the "
                    f"TPU tiling rule (last two block dims divisible by "
                    f"{TPU_TILE} or equal to the array's); compiled "
                    f"Pallas cannot run this plan")


def _check_compiled_pallas(plan: KernelPlan) -> None:
    """Every guard a plan must pass before compiled (non-interpret)
    Pallas runs it."""
    _check_compiled_revisit_order(plan)
    _check_tpu_tiling(plan)


def _first_visit(plan: KernelPlan):
    """Predicate: this grid step is the first visit to the current output
    block (all output-irrelevant grid axes at 0)."""
    rel = plan.layer.tensors["O"]
    pred = None
    for i, ax in enumerate(plan.grid):
        if ax.dim not in rel:
            p = pl.program_id(i) == 0
            pred = p if pred is None else jnp.logical_and(pred, p)
    return True if pred is None else pred


def _init_when(pred, fn) -> None:
    """Run ``fn`` under ``pl.when(pred)``; unconditionally when the output
    block is only ever visited once (no reduction grid axes)."""
    if pred is True:
        fn()
    else:
        pl.when(pred)(fn)


# ---------------------------------------------------------------------------
# matmul / fc
# ---------------------------------------------------------------------------

def _run_fc(plan: KernelPlan, x: jnp.ndarray, w: jnp.ndarray,
            interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    bn, bc, bk = plan.block["N"], plan.block["C"], plan.block["K"]

    def kern(x_ref, w_ref, o_ref):
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
        _init_when(_first_visit(plan), _init)
        o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                              preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[
            pl.BlockSpec((bn, bc), plan.index_map(("N", "C"))),
            pl.BlockSpec((bc, bk), plan.index_map(("C", "K"))),
        ],
        out_specs=pl.BlockSpec((bn, bk), plan.index_map(("N", "K"))),
        out_shape=jax.ShapeDtypeStruct((layer.dim("N"), layer.dim("K")),
                                       jnp.float32),
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# conv
# ---------------------------------------------------------------------------

def _run_conv(plan: KernelPlan, x: jnp.ndarray, w: jnp.ndarray,
              interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    R = int(layer.meta["R"])
    S = int(layer.meta["S"])
    stride = int(layer.meta["stride"])
    N, C, K = layer.dim("N"), layer.dim("C"), layer.dim("K")
    XO, YO = layer.dim("X"), layer.dim("Y")
    XI, YI = x.shape[2], x.shape[3]
    bn, bc, bk = plan.block["N"], plan.block["C"], plan.block["K"]
    bx, by = plan.block["X"], plan.block["Y"]
    spanx = (bx - 1) * stride + R
    spany = (by - 1) * stride + S
    x_axis, y_axis = plan.axis_of("X"), plan.axis_of("Y")

    def kern(x_ref, w_ref, o_ref):
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
        _init_when(_first_visit(plan), _init)
        ix = pl.program_id(x_axis) if x_axis >= 0 else 0
        iy = pl.program_id(y_axis) if y_axis >= 0 else 0
        xin = x_ref[...]                       # [bn, bc, XI, YI]
        xw = jax.lax.dynamic_slice(
            xin, (0, 0, ix * bx * stride, iy * by * stride),
            (bn, bc, spanx, spany))
        acc = jnp.zeros((bn, bk, bx, by), jnp.float32)
        for r in range(R):                     # R, S pinned in-block, as in
            for s in range(S):                 # the directive model
                patch = jax.lax.slice(
                    xw, (0, 0, r, s),
                    (bn, bc, r + (bx - 1) * stride + 1,
                     s + (by - 1) * stride + 1),
                    (1, 1, stride, stride))    # [bn, bc, bx, by]
                acc += jnp.einsum("ncxy,kc->nkxy", patch, w_ref[:, :, r, s],
                                  preferred_element_type=jnp.float32)
        o_ref[...] += acc

    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[
            # halo'd input: blocked over N/C, full spatial extent streamed
            pl.BlockSpec((bn, bc, XI, YI), plan.index_map(("N", "C", "*",
                                                           "*"))),
            pl.BlockSpec((bk, bc, R, S), plan.index_map(("K", "C", "*",
                                                         "*"))),
        ],
        out_specs=pl.BlockSpec((bn, bk, bx, by),
                               plan.index_map(("N", "K", "X", "Y"))),
        out_shape=jax.ShapeDtypeStruct((N, K, XO, YO), jnp.float32),
        interpret=interpret,
    )(x, w)


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# pool (max pooling; every grid axis is output-relevant: single visit)
# ---------------------------------------------------------------------------

def _run_pool(plan: KernelPlan, x: jnp.ndarray,
              interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    R = int(layer.meta["R"])
    S = int(layer.meta["S"])
    stride = int(layer.meta["stride"])
    N, C = layer.dim("N"), layer.dim("C")
    XO, YO = layer.dim("X"), layer.dim("Y")
    XI, YI = x.shape[2], x.shape[3]
    bn, bc = plan.block["N"], plan.block["C"]
    bx, by = plan.block["X"], plan.block["Y"]
    spanx = (bx - 1) * stride + R
    spany = (by - 1) * stride + S
    x_axis, y_axis = plan.axis_of("X"), plan.axis_of("Y")

    def kern(x_ref, o_ref):
        ix = pl.program_id(x_axis) if x_axis >= 0 else 0
        iy = pl.program_id(y_axis) if y_axis >= 0 else 0
        xw = jax.lax.dynamic_slice(
            x_ref[...], (0, 0, ix * bx * stride, iy * by * stride),
            (bn, bc, spanx, spany))
        acc = jnp.full((bn, bc, bx, by), NEG_INF, jnp.float32)
        for r in range(R):                     # window pinned in-block, like
            for s in range(S):                 # conv's R/S
                patch = jax.lax.slice(
                    xw, (0, 0, r, s),
                    (bn, bc, r + (bx - 1) * stride + 1,
                     s + (by - 1) * stride + 1),
                    (1, 1, stride, stride))
                acc = jnp.maximum(acc, patch)
        o_ref[...] = acc

    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[
            # halo'd input: blocked over N/C, full spatial extent streamed
            pl.BlockSpec((bn, bc, XI, YI), plan.index_map(("N", "C", "*",
                                                           "*"))),
        ],
        out_specs=pl.BlockSpec((bn, bc, bx, by),
                               plan.index_map(("N", "C", "X", "Y"))),
        out_shape=jax.ShapeDtypeStruct((N, C, XO, YO), jnp.float32),
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# eltwise (n-ary sum; residual adds, gate merges, channel-embedded concat)
# ---------------------------------------------------------------------------

def _run_eltwise(plan: KernelPlan, xs: Sequence[jnp.ndarray],
                 interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    shape = tuple(layer.dim(d) for d in ("N", "C", "X", "Y"))
    bshape = tuple(plan.block[d] for d in ("N", "C", "X", "Y"))

    def kern(*refs):
        acc = refs[0][...].astype(jnp.float32)
        for r in refs[1:-1]:
            acc = acc + r[...]
        refs[-1][...] = acc

    spec = pl.BlockSpec(bshape, plan.index_map(("N", "C", "X", "Y")))
    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[spec] * len(xs),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        interpret=interpret,
    )(*xs)


# ---------------------------------------------------------------------------
# attention (flash-style online softmax over KV-position blocks)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def rope_tables(seq: int, dim: int, theta: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(cos, sin) [seq, dim // 2] of RoPE's angles pos * theta ** (-2i /
    dim), computed in float64 on the host and rounded to float32."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate x [..., S, D] by position (half-split pairs (i, i + D/2))."""
    S, D = x.shape[-2], x.shape[-1]
    cos, sin = rope_tables(S, D, float(theta))
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention_inputs(layer, q: jnp.ndarray, k: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q and k [N, S, D] rotated by RoPE when the layer's meta sets a
    ``rope_theta`` (unchanged otherwise)."""
    theta = float(layer.meta.get("rope_theta", 0.0))
    return (rope(q, theta), rope(k, theta)) if theta else (q, k)


def _run_attention(plan: KernelPlan, q: jnp.ndarray, k: jnp.ndarray,
                   v: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    NH, Sq, Skv = layer.dim("N"), layer.dim("X"), layer.dim("C")
    D = layer.dim("K")
    bn, bx, bc = plan.block["N"], plan.block["X"], plan.block["C"]
    scale = D ** -0.5
    causal = bool(layer.meta.get("causal", 0))
    x_axis, c_axis = plan.axis_of("X"), plan.axis_of("C")
    q, k = attention_inputs(layer, q, k)

    def kern(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref):
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        _init_when(_first_visit(plan), _init)
        s = jnp.einsum("nqd,nkd->nqk", q_ref[...], k_ref[...],
                       preferred_element_type=jnp.float32) * scale
        if causal:                  # key positions after the query's
            ix = pl.program_id(x_axis) if x_axis >= 0 else 0
            ic = pl.program_id(c_axis) if c_axis >= 0 else 0
            qpos = ix * bx + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bx, bc), 0)
            kpos = ic * bc + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bx, bc), 1)
            keep = (kpos <= qpos + (Skv - Sq))[None]
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        if causal:                  # a block masked whole adds nothing
            p = jnp.where(keep, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[..., None] + \
            jnp.einsum("nqk,nkd->nqd", p, v_ref[...],
                       preferred_element_type=jnp.float32)

    acc, _m, lsum = pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[
            pl.BlockSpec((bn, bx, D), plan.index_map(("N", "X", "*"))),
            pl.BlockSpec((bn, bc, D), plan.index_map(("N", "C", "*"))),
            pl.BlockSpec((bn, bc, D), plan.index_map(("N", "C", "*"))),
        ],
        out_specs=[
            pl.BlockSpec((bn, bx, D), plan.index_map(("N", "X", "*"))),
            pl.BlockSpec((bn, bx), plan.index_map(("N", "X"))),
            pl.BlockSpec((bn, bx), plan.index_map(("N", "X"))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((NH, Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((NH, Sq), jnp.float32),
            jax.ShapeDtypeStruct((NH, Sq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return acc / jnp.maximum(lsum, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# RMSNorm (the channel row is block-resident: every grid axis is over N)
# ---------------------------------------------------------------------------

def _run_norm(plan: KernelPlan, x: jnp.ndarray, g: jnp.ndarray,
              interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    N, C = layer.dim("N"), layer.dim("C")
    bn = plan.block["N"]
    eps = float(layer.meta["eps"])

    def kern(x_ref, g_ref, o_ref):
        xb = x_ref[...]
        ms = jnp.mean(xb * xb, axis=-1, keepdims=True)
        o_ref[...] = xb * jax.lax.rsqrt(ms + eps) * g_ref[...]

    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[pl.BlockSpec((bn, C), plan.index_map(("N", "C"))),
                  pl.BlockSpec((1, C), plan.index_map(("*", "C")))],
        out_specs=pl.BlockSpec((bn, C), plan.index_map(("N", "C"))),
        out_shape=jax.ShapeDtypeStruct((N, C), jnp.float32),
        interpret=interpret,
    )(x, g.reshape(1, C))


# ---------------------------------------------------------------------------
# SwiGLU product (elementwise over [N, C]; gate and up blocked alike)
# ---------------------------------------------------------------------------

def _run_glu(plan: KernelPlan, x: jnp.ndarray,
             interpret: bool) -> jnp.ndarray:
    layer = plan.layer
    N, C = layer.dim("N"), layer.dim("C")
    bn, bc = plan.block["N"], plan.block["C"]
    gate, up = x[:, :C], x[:, C:]

    def kern(g_ref, u_ref, o_ref):
        g = g_ref[...]
        o_ref[...] = g * jax.nn.sigmoid(g) * u_ref[...]

    spec = pl.BlockSpec((bn, bc), plan.index_map(("N", "C")))
    return pl.pallas_call(
        kern,
        grid=_grid(plan),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((N, C), jnp.float32),
        interpret=interpret,
    )(gate, up)


# ---------------------------------------------------------------------------
# Public API: kernel maps, inputs, execution, verification, measurement
# ---------------------------------------------------------------------------

def pallas_kernels(interpret: bool) -> Dict[str, Callable]:
    """kind -> Pallas kernel ``kernel(plan, *operands)``, interpreted or
    compiled as ``interpret`` says."""
    bind = functools.partial
    return {"conv": bind(_run_conv, interpret=interpret),
            "fc": bind(_run_fc, interpret=interpret),
            "attention": bind(_run_attention, interpret=interpret),
            "pool": bind(_run_pool, interpret=interpret),
            "eltwise": lambda plan, *xs: _run_eltwise(plan, xs, interpret),
            "norm": bind(_run_norm, interpret=interpret),
            "glu": bind(_run_glu, interpret=interpret)}


def make_inputs(plan: KernelPlan, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Deterministic dense float32 inputs in the plan's canonical layouts
    (``kinds.LayerKind.plan_operands``): the i-th activation operand is a
    standard normal from the i-th key of ``seed``, and the weight, for a
    kind that has one, comes from the next key through the kind's
    initialiser."""
    layer = plan.layer
    kind = kind_of(plan.kind)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = kind.plan_operands(layer)
    inputs = {name: jax.random.normal(key, shape, jnp.float32)
              for key, (name, shape) in zip(keys, shapes.items())}
    if kind.weight is not None:
        inputs["W"] = kind.weight(keys[len(shapes)], layer)
    return inputs


def _kernels(backend: str) -> Dict[str, Callable]:
    if backend == "compiled":
        from .fuse import compiled_kernels     # lazy: fuse imports netexec
        return compiled_kernels()
    return pallas_kernels(backend_interprets(backend))


def plan_fn(plan: KernelPlan, backend: str
            ) -> Tuple[Callable, Tuple[str, ...]]:
    """(fn, input names): ``fn(*inputs)`` runs the plan on its kind's
    kernel under ``backend``, looked up when ``fn`` runs (or traces).
    Refuses an invalid plan, and under ``pallas`` a plan the compiled
    Pallas guards refuse, naming the layer."""
    if not plan.valid:
        raise ValueError(
            f"cannot execute invalid plan for layer {plan.layer.name!r}: "
            f"{plan.invalid_reason}")
    if backend == "pallas":
        _check_compiled_pallas(plan)

    def fn(*xs):
        return _kernels(backend)[plan.kind](plan, *xs)
    return fn, operand_names(plan.layer)


def plan_runner(plan: KernelPlan, jit: bool = False,
                backend: str = ORACLE_BACKEND):
    """Build a callable ``inputs_dict -> output`` for the plan.  With
    ``jit=True`` the kernel is staged once and re-invocations time the
    compiled executable (the measurement path).  ``interpret`` and
    ``pallas`` run the Pallas kernel, ``compiled`` the fused tier's XLA
    kernel (``kernels.backend``)."""
    fn, names = plan_fn(plan, resolve_backend(backend))
    fn = jax.jit(fn) if jit else fn
    return lambda inputs: fn(*(inputs[n] for n in names))


def execute_plan(plan: KernelPlan, inputs: Optional[Dict] = None,
                 seed: int = 0,
                 backend: str = ORACLE_BACKEND) -> jnp.ndarray:
    """Run the plan on ``backend`` and return the output."""
    run = plan_runner(plan, backend=backend)  # refuses invalid plans first,
    inputs = inputs if inputs is not None else make_inputs(plan, seed)
    return run(inputs)                        # naming the layer + reason


def attention_reference(layer, q: jnp.ndarray, k: jnp.ndarray,
                        v: jnp.ndarray) -> jnp.ndarray:
    """The ``kernels/ref.py`` oracle of one attention layer over [N, S,
    D], with RoPE and the causal mask as the layer's meta says."""
    q, k = q[:, None], k[:, None]
    theta = float(layer.meta.get("rope_theta", 0.0))
    if theta:
        q, k = ref.rope_ref(q, theta), ref.rope_ref(k, theta)
    return ref.attention_ref(q, k, v[:, None],
                             causal=bool(layer.meta.get("causal", 0)))[:, 0]


#: kind -> oracle ``kernel(plan, *operands)`` from ``kernels/ref.py``
ORACLE_KERNELS: Dict[str, Callable] = {
    "conv": lambda plan, x, w: ref.conv2d_ref(
        x, w, stride=int(plan.layer.meta["stride"])),
    "fc": lambda plan, x, w: ref.matmul_ref(x, w),
    "attention": lambda plan, q, k, v: attention_reference(plan.layer, q,
                                                           k, v),
    "pool": lambda plan, x: ref.pool2d_ref(
        x, int(plan.layer.meta["R"]), int(plan.layer.meta["S"]),
        stride=int(plan.layer.meta["stride"])),
    "eltwise": lambda plan, *xs: ref.eltwise_ref(*xs),
    "norm": lambda plan, x, g: ref.rmsnorm_ref(x, g,
                                               float(plan.layer.meta["eps"])),
    "glu": lambda plan, x: ref.glu_ref(x),
}


def reference_output(plan: KernelPlan, inputs: Dict) -> jnp.ndarray:
    """Ground truth from ``kernels/ref.py`` for the plan's layer."""
    names = operand_names(plan.layer)
    return ORACLE_KERNELS[plan.kind](plan, *(inputs[n] for n in names))


#: the one tolerance every backend is held to against the ``kernels/ref.py``
#: oracle (``rel_error`` below).  The oracle and the compiled tier both
#: compute f32 at HIGHEST matmul precision (``fuse.MATMUL_PRECISION``);
#: on the CPU they agree to ~1e-6 (BENCH_network.json: worst 2.3e-6),
#: while a wrong kernel — one dropped filter tap of a 3x3 conv — is off
#: by ~1e-1.
ORACLE_TOL = 1e-3


def rel_error(out, want) -> float:
    a = np.asarray(out, np.float32)
    b = np.asarray(want, np.float32)
    return float(np.max(np.abs(a - b)) / (np.abs(b).max() + 1e-9))


def verify_plan(plan: KernelPlan, backend: str = ORACLE_BACKEND,
                seed: int = 0,
                tol: float = ORACLE_TOL) -> Tuple[bool, float]:
    """Execute the plan on ``backend`` and compare against the oracle.
    Returns (ok, max relative error)."""
    inputs = make_inputs(plan, seed)
    out = execute_plan(plan, inputs, backend=backend)
    err = rel_error(out, reference_output(plan, inputs))
    return err < tol, err


def measure_plan(plan: KernelPlan, inputs: Optional[Dict] = None,
                 backend: str = ORACLE_BACKEND, iters: int = 2,
                 warmup: int = 1) -> float:
    """Measured wall-clock seconds for one plan execution (min over
    ``iters`` after ``warmup`` runs; ``block_until_ready`` fences).

    Measures the jitted executable so the time reflects the plan's
    actual compute/memory work, not per-call tracing overhead
    (compilation happens during warmup)."""
    inputs = inputs if inputs is not None else make_inputs(plan)
    run = plan_runner(plan, jit=True, backend=backend)
    for _ in range(max(1, warmup)):
        jax.block_until_ready(run(inputs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run(inputs))
        best = min(best, time.perf_counter() - t0)
    return best
