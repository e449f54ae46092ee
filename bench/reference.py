"""The plain reference forward and the comparison that decides ``correct``.

The reference runs a configuration's own layer list (``configs/<arch>.py``)
in straightforward ``jax.numpy``, one layer at a time, in float32 with
every product at ``Precision.HIGHEST``, as the configurations state.  It
imports nothing of the program.  How a producer's output feeds its
consumer follows the layer graph's data contract, implemented here anew:

1. equal per-image size: reshape (flatten before an fc layer);
2. else, equal channels of two 4-D tensors: centered zero pad or crop of
   the spatial dims to the extent the consumer's VALID window needs;
3. an eltwise layer sums its sources.

``precision="high"`` is a control: the same forward with every conv and
fc product taken in three bf16 passes (``Precision.HIGH`` on a TPU),
written out as an explicit split so that it computes the same on any
platform.  It is the step below what the configurations state, and the
comparison has to fail it.  On the chip the program's own path at that
precision (``fuse.MATMUL_PRECISION = HIGH``) is read as the other
control (``tools/limits.py``).

``compare`` runs the reference over one set of inputs and measures, for
every layer output the program returned, the largest absolute gap to the
reference over the reference's largest magnitude.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Mapping, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def _bf16(x: jnp.ndarray) -> jnp.ndarray:
    # reduce_precision, not a round trip through bfloat16: XLA on the TPU
    # may keep the excess precision of a convert pair and skip the rounding
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _product(op, x, w, precision: str):
    if precision == "highest":
        return op(x, w)
    # bf16_3x: the two cross terms, then the leading term; lo*lo dropped
    xh, xl = _split(x)
    wh, wl = _split(w)
    return (op(xl, wh) + op(xh, wl)) + op(xh, wh)


@functools.partial(jax.jit, static_argnames=("stride", "precision"))
def conv(x, w, stride: int, precision: str):
    def op(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    return _product(op, x, w, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def fc(x, w, precision: str):
    return _product(lambda a, b: jnp.dot(a, b, precision=HIGHEST), x, w,
                    precision)


@functools.partial(jax.jit, static_argnames=("r", "s", "stride"))
def pool(x, r: int, s: int, stride: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, r, s),
                                 (1, 1, stride, stride), "VALID")


@jax.jit
def add(xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


@functools.partial(jax.jit, static_argnames=("shape",))
def fit(x, shape: Tuple[int, ...]):
    """Rules 1 and 2 of the data contract (module docstring)."""
    if x.shape == shape:
        return x
    per_src, per_dst = 1, 1
    for d in x.shape[1:]:
        per_src *= d
    for d in shape[1:]:
        per_dst *= d
    if per_src == per_dst:
        return x.reshape(shape)
    if x.ndim != 4 or len(shape) != 4 or x.shape[1] != shape[1]:
        raise ValueError(f"no data contract from {x.shape} to {shape}")
    for ax in (2, 3):
        d = shape[ax] - x.shape[ax]
        if d > 0:
            pad = [(0, 0)] * 4
            pad[ax] = (d // 2, d - d // 2)
            x = jnp.pad(x, pad)
        elif d < 0:
            lo = (-d) // 2
            x = jax.lax.slice_in_dim(x, lo, lo + shape[ax], axis=ax)
    return x


@jax.jit
def gap(out, want):
    """(largest |out - want|, largest |want|, every element of out finite)"""
    return (jnp.max(jnp.abs(out - want)), jnp.max(jnp.abs(want)),
            jnp.all(jnp.isfinite(out)))


def input_shape(layer: Mapping) -> Tuple[int, ...]:
    """The input a layer's kernel takes, with its VALID window's halo."""
    n, c = layer["N"], layer["C"]
    if layer["kind"] == "fc":
        return (n, c)
    if layer["kind"] in ("conv", "pool"):
        st = layer["stride"]
        return (n, c, (layer["X"] - 1) * st + layer["R"],
                (layer["Y"] - 1) * st + layer["S"])
    return (n, c, layer["X"], layer["Y"])


def weight_shape(layer: Mapping) -> Tuple[int, ...]:
    if layer["kind"] == "conv":
        return (layer["K"], layer["C"], layer["R"], layer["S"])
    return (layer["C"], layer["K"])


def feeds(layers: Iterable[Mapping]) -> Dict[str, Tuple[Tuple[int, ...],
                                                        float]]:
    """Every array a forward takes, as ``name -> (shape, scale)``: a
    ``<layer>.I`` image batch for each layer with no source and a
    ``<layer>.W`` weight for each conv and fc layer, scaled by
    ``fan_in ** -0.5`` so that activations stay near 1 through depth."""
    out = {}
    for l in layers:
        if not l["src"]:
            out[f"{l['name']}.I"] = (input_shape(l), 1.0)
        if l["kind"] in ("conv", "fc"):
            shp = weight_shape(l)
            fan_in = l["C"] * (l["R"] * l["S"] if l["kind"] == "conv"
                               else 1)
            out[f"{l['name']}.W"] = (shp, fan_in ** -0.5)
    return out


def layer_out(layer: Mapping, srcs: List[jnp.ndarray], arrays: Mapping,
              precision: str) -> jnp.ndarray:
    name, kind = layer["name"], layer["kind"]
    xs = srcs if srcs else [arrays[f"{name}.I"]]
    shape = input_shape(layer)
    if kind == "eltwise":
        return add(tuple(fit(x, shape) for x in xs))
    x = fit(xs[0], shape)
    if kind == "conv":
        return conv(x, arrays[f"{name}.W"], layer["stride"], precision)
    if kind == "fc":
        return fc(x, arrays[f"{name}.W"], precision)
    if kind == "pool":
        return pool(x, layer["R"], layer["S"], layer["stride"])
    raise ValueError(f"no reference for kind {kind!r}")


def walk(layers: List[Mapping], arrays: Mapping, precision: str):
    """Yield ``(name, output)`` layer by layer.  Each output is freed once
    its last consumer has run, so the walk holds little besides its
    inputs."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    left = {l["name"]: 0 for l in layers}
    for l in layers:
        for s in l["src"]:
            left[s] += 1
    vals: Dict[str, jnp.ndarray] = {}
    for l in layers:
        name = l["name"]
        y = layer_out(l, [vals[s] for s in l["src"]], arrays, precision)
        yield name, y
        for s in l["src"]:
            left[s] -= 1
            if left[s] == 0:
                del vals[s]
        if left[name]:
            vals[name] = y


def forward(layers: List[Mapping], arrays: Mapping, names: Iterable[str],
            precision: str = "highest") -> Dict[str, jnp.ndarray]:
    """The reference's outputs of the layers ``names``."""
    names = set(names)
    return {n: y for n, y in walk(layers, arrays, precision) if n in names}


def compare(layers: List[Mapping], arrays: Mapping,
            outputs: Mapping[str, jnp.ndarray],
            precision: str = "highest") -> Dict[str, float]:
    """For every name in ``outputs``, the largest gap to the reference
    relative to the reference's largest magnitude (``inf`` for a
    non-finite output or a shape that differs)."""
    errs: Dict[str, float] = {}
    for name, y in walk(layers, arrays, precision):
        if name not in outputs:
            continue
        got = outputs[name]
        if tuple(got.shape) != tuple(y.shape):
            errs[name] = float("inf")
            continue
        d, m, finite = (float(v) for v in gap(got, y))
        errs[name] = d / m if finite and m > 0 else float("inf")
    return errs
