"""ResNet-50 as published (He et al., arXiv:1512.03385, Table 1, 50-layer),
written out as the layer list the plain reference runs.

Stem: 7x7/2 conv to 64 channels, 3x3/2 max pool.  Four stages of
bottleneck blocks (1x1, 3x3, 1x1) with a projection shortcut on each
stage's first block; the stride of a stage sits on the first 1x1 conv
and on the projection, as in the paper's original (v1) form.  Then a
global 7x7 pool and the 1000-way fc.  Departures, as the configuration
file lists under ``assumed``: no batch norm or ReLU, and the global pool
takes the maximum, since the scheduler's layer model has conv, pool,
eltwise and fc layers only.
"""
from __future__ import annotations

from typing import Dict, List


def _conv(name, n, c, k, x, r, stride=1, src=()):
    return {"name": name, "kind": "conv", "N": n, "C": c, "K": k, "X": x,
            "Y": x, "R": r, "S": r, "stride": stride, "src": list(src),
            "srcs": len(src)}


def _pool(name, n, c, x, r, stride, src):
    return {"name": name, "kind": "pool", "N": n, "C": c, "K": 1, "X": x,
            "Y": x, "R": r, "S": r, "stride": stride, "src": list(src),
            "srcs": len(src)}


def layers(cfg: Dict, batch: int) -> List[Dict]:
    n = batch
    stem = cfg["stem"]
    x = cfg["image"] // stem["stride"]
    out = [_conv("conv1", n, cfg["in_channels"], stem["channels"], x,
                 stem["kernel"], stem["stride"])]
    x //= stem["pool_stride"]
    out.append(_pool("pool1", n, stem["channels"], x, stem["pool_kernel"],
                     stem["pool_stride"], ["conv1"]))
    prev, c_in = "pool1", stem["channels"]
    for s, (blocks, c_mid, c_out) in enumerate(cfg["stages"]):
        if s > 0:
            x //= 2
        for b in range(blocks):
            nm = f"r{s + 2}{chr(ord('a') + b)}"
            st = 2 if (b == 0 and s > 0) else 1
            out.append(_conv(f"{nm}.a", n, c_in, c_mid, x, 1, st, [prev]))
            out.append(_conv(f"{nm}.b", n, c_mid, c_mid, x, 3, 1,
                             [f"{nm}.a"]))
            out.append(_conv(f"{nm}.c", n, c_mid, c_out, x, 1, 1,
                             [f"{nm}.b"]))
            if b == 0:
                out.append(_conv(f"{nm}.p", n, c_in, c_out, x, 1, st,
                                 [prev]))
                short = f"{nm}.p"
            else:
                short = prev
            out.append({"name": f"{nm}.add", "kind": "eltwise", "N": n,
                        "C": c_out, "K": 1, "X": x, "Y": x,
                        "src": [f"{nm}.c", short], "srcs": 2})
            prev, c_in = f"{nm}.add", c_out
    out.append(_pool("gap", n, c_in, 1, x, x, [prev]))
    out.append({"name": "fc", "kind": "fc", "N": n, "C": c_in,
                "K": cfg["classes"], "X": 1, "Y": 1, "src": ["gap"],
                "srcs": 1})
    return out
