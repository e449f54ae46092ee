"""Work per layer, counted from layer shapes: the benchmark's yardstick.

A layer is described by a plain mapping with ``kind`` (``conv``, ``fc``,
``pool`` or ``eltwise``), the loop sizes ``N``, ``C``, ``K``, ``X``,
``Y`` (output extent), the window ``R``, ``S`` and ``stride``, and
``srcs``, the number of summands of an ``eltwise`` layer.  ``from_spec``
turns one of the program's ``LayerSpec`` objects into that form, so the
harness can count the program's graph and its own reference alike.

Counted is what the algorithm needs, not what an implementation does:

* ``macs``: multiply-accumulates of conv and fc layers.  Pool and
  eltwise layers do no multiply-accumulate and count 0 here.
* ``flops``: 2 per MAC for conv and fc; one compare per window point
  for pool; one add per summand after the first for eltwise.
* ``min_bytes``: every operand read once and the output written once,
  at ``dtype_bytes`` per element.
"""
from __future__ import annotations

from typing import Iterable, Mapping


def input_extent(layer: Mapping) -> tuple:
    """Spatial input extent a VALID window needs: (X-1)*stride + R."""
    st = int(layer.get("stride", 1))
    return ((int(layer["X"]) - 1) * st + int(layer["R"]),
            (int(layer["Y"]) - 1) * st + int(layer["S"]))


def macs(layer: Mapping) -> int:
    kind = layer["kind"]
    if kind == "conv":
        return (int(layer["N"]) * int(layer["K"]) * int(layer["C"])
                * int(layer["X"]) * int(layer["Y"])
                * int(layer["R"]) * int(layer["S"]))
    if kind == "fc":
        return int(layer["N"]) * int(layer["C"]) * int(layer["K"])
    if kind in ("pool", "eltwise"):
        return 0
    raise ValueError(f"no work count for kind {kind!r}")


def flops(layer: Mapping) -> int:
    kind = layer["kind"]
    if kind in ("conv", "fc"):
        return 2 * macs(layer)
    out = (int(layer["N"]) * int(layer["C"]) * int(layer["X"])
           * int(layer["Y"]))
    if kind == "pool":
        return out * int(layer["R"]) * int(layer["S"])
    if kind == "eltwise":
        return out * (int(layer["srcs"]) - 1)
    raise ValueError(f"no work count for kind {kind!r}")


def min_bytes(layer: Mapping, dtype_bytes: int = 4) -> int:
    kind = layer["kind"]
    N, C = int(layer["N"]), int(layer["C"])
    if kind == "fc":
        K = int(layer["K"])
        elems = N * C + C * K + N * K
    elif kind == "conv":
        K = int(layer["K"])
        XI, YI = input_extent(layer)
        elems = (N * C * XI * YI + K * C * int(layer["R"]) * int(layer["S"])
                 + N * K * int(layer["X"]) * int(layer["Y"]))
    elif kind == "pool":
        XI, YI = input_extent(layer)
        elems = N * C * (XI * YI + int(layer["X"]) * int(layer["Y"]))
    elif kind == "eltwise":
        elems = (N * C * int(layer["X"]) * int(layer["Y"])
                 * (int(layer["srcs"]) + 1))
    else:
        raise ValueError(f"no work count for kind {kind!r}")
    return elems * dtype_bytes


def total_macs(layers: Iterable[Mapping]) -> int:
    """Conv and fc MACs of a whole forward."""
    return sum(macs(l) for l in layers)


def from_spec(spec) -> dict:
    """The counting form of a program ``LayerSpec``: its loop sizes,
    window and source count, as the layer builders set them."""
    d = {"name": spec.name, "kind": spec.kind,
         "N": spec.dim("N"), "C": spec.dim("C"), "K": spec.dim("K"),
         "X": spec.dim("X"), "Y": spec.dim("Y"),
         "srcs": len(spec.src), "src": list(spec.src)}
    for k in ("R", "S", "stride"):
        if k in spec.meta:
            d[k] = int(spec.meta[k])
    return d
