"""The layer kinds the lowering tier executes: one record per kind.

A record says what the executors need to know of a kind beyond its
kernels:

  * ``input_shape``: the canonical activation a network feeds the
    kernel (for attention, its ``qkv`` source's token-major output);
  * ``operands``: the activation operands of a lone plan, by name and
    shape, in kernel order (default: one ``"I"`` of ``input_shape``);
  * ``weight``: the initialiser of the layer's ``"W"`` array, drawn from
    the caller's key, or None for a kind with no weights;
  * ``sums_sources`` / ``heads``: how the graph walk
    (``netexec.layer_output``) hands the kernel its operands: every
    source aligned for an n-ary sum, or Q, K and V split out of a
    ``qkv`` source with the heads merged back after.

Every kernel of every tier is called ``kernel(plan, *operands)``, with
the weight last for a kind that has one.  The tiers' maps from kind to
kernel are ``exec.pallas_kernels``, ``exec.ORACLE_KERNELS`` and
``fuse.compiled_kernels``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..workloads.layers import LayerSpec

Shape = Tuple[int, ...]


def input_extent(layer: LayerSpec) -> Tuple[int, int]:
    """Minimal halo'd spatial input extent of a conv/pool layer under
    VALID padding: (X-1)*stride + R."""
    R, S = int(layer.meta["R"]), int(layer.meta["S"])
    stride = int(layer.meta["stride"])
    return ((layer.dim("X") - 1) * stride + R,
            (layer.dim("Y") - 1) * stride + S)


def heads(layer: LayerSpec) -> Tuple[int, int]:
    """(query heads, K/V heads) of an attention layer."""
    h = int(layer.meta["heads"])
    return h, int(layer.meta.get("kv_heads", h))


def _rows(layer: LayerSpec) -> Shape:
    return (layer.dim("N"), layer.dim("C"))


def _halo(layer: LayerSpec) -> Shape:
    return (layer.dim("N"), layer.dim("C")) + input_extent(layer)


def _nchw(layer: LayerSpec) -> Shape:
    return tuple(layer.dim(d) for d in ("N", "C", "X", "Y"))


def _gate_up(layer: LayerSpec) -> Shape:
    return (layer.dim("N"), 2 * layer.dim("C"))


def _qkv(layer: LayerSpec) -> Shape:
    h, kv = heads(layer)
    return (int(layer.meta["batch"]) * layer.dim("X"),
            (h + 2 * kv) * layer.dim("K"))


def _qkv_operands(layer: LayerSpec) -> Dict[str, Shape]:
    NH, D = layer.dim("N"), layer.dim("K")
    return {"Q": (NH, layer.dim("X"), D), "K": (NH, layer.dim("C"), D),
            "V": (NH, layer.dim("C"), D)}


def _fc_weight(key: jax.Array, layer: LayerSpec) -> jnp.ndarray:
    return jax.random.normal(key, (layer.dim("C"), layer.dim("K")),
                             jnp.float32) * layer.dim("C") ** -0.5


def _conv_weight(key: jax.Array, layer: LayerSpec) -> jnp.ndarray:
    R, S = int(layer.meta["R"]), int(layer.meta["S"])
    fan_in = layer.dim("C") * R * S
    return jax.random.normal(
        key, (layer.dim("K"), layer.dim("C"), R, S),
        jnp.float32) * fan_in ** -0.5


def _norm_gain(key: jax.Array, layer: LayerSpec) -> jnp.ndarray:
    return 1.0 + 0.1 * jax.random.normal(key, (layer.dim("C"),),
                                         jnp.float32)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What the executors know of one layer kind (module docstring)."""

    input_shape: Callable[[LayerSpec], Shape]
    weight: Optional[Callable[[jax.Array, LayerSpec], jnp.ndarray]] = None
    operands: Optional[Callable[[LayerSpec], Dict[str, Shape]]] = None
    sums_sources: bool = False
    heads: bool = False

    def plan_operands(self, layer: LayerSpec) -> Dict[str, Shape]:
        """The lone plan's activation operands, by name, in kernel
        order."""
        if self.operands is not None:
            return self.operands(layer)
        return {"I": self.input_shape(layer)}


KINDS: Dict[str, LayerKind] = {
    "conv": LayerKind(_halo, weight=_conv_weight),
    "fc": LayerKind(_rows, weight=_fc_weight),
    "attention": LayerKind(_qkv, operands=_qkv_operands, heads=True),
    "pool": LayerKind(_halo),
    "eltwise": LayerKind(_nchw, sums_sources=True,
                         operands=lambda l: {"A": _nchw(l), "B": _nchw(l)}),
    "norm": LayerKind(_rows, weight=_norm_gain),
    "glu": LayerKind(_gate_up),
}

#: kinds fed a ``<owner>.W`` weight array
WEIGHT_KINDS = tuple(k for k, rec in KINDS.items() if rec.weight)


def kind_of(kind: str) -> LayerKind:
    """The record of ``kind``; a ValueError for a kind that no tier
    executes."""
    if kind not in KINDS:
        raise ValueError(f"unsupported kind {kind!r}")
    return KINDS[kind]


def required_input_shape(layer: LayerSpec) -> Shape:
    """Canonical input-activation shape each kernel consumes (for an
    attention layer, its ``qkv`` source's token-major output)."""
    return kind_of(layer.kind).input_shape(layer)


def operand_names(layer: LayerSpec) -> Tuple[str, ...]:
    """Names of a lone plan's inputs, in kernel order: the activation
    operands, then ``"W"`` for a kind with weights."""
    rec = kind_of(layer.kind)
    return tuple(rec.plan_operands(layer)) + (("W",) if rec.weight else ())


__all__ = ["LayerKind", "KINDS", "WEIGHT_KINDS", "kind_of",
           "required_input_shape", "operand_names", "input_extent",
           "heads"]
