"""VGG-16 as published (Simonyan and Zisserman, arXiv:1409.1556,
configuration D), written out as the layer list the plain reference runs.

Five blocks of 3x3 stride-1 convs padded to keep the spatial size, each
block closed by a 2x2/2 max pool; then fc-4096, fc-4096 and fc-1000.
Departure, as the configuration file lists under ``assumed``: no ReLU
(and no dropout), since the scheduler's layer model has conv, pool,
eltwise and fc layers only.
"""
from __future__ import annotations

from typing import Dict, List


def layers(cfg: Dict, batch: int) -> List[Dict]:
    n, x, c_in = batch, cfg["image"], cfg["in_channels"]
    out: List[Dict] = []
    prev = None
    for b, (convs, ch) in enumerate(cfg["blocks"]):
        for i in range(convs):
            nm = f"conv{b + 1}_{i + 1}"
            src = [prev] if prev else []
            out.append({"name": nm, "kind": "conv", "N": n, "C": c_in,
                        "K": ch, "X": x, "Y": x, "R": 3, "S": 3,
                        "stride": 1, "src": src, "srcs": len(src)})
            prev, c_in = nm, ch
        x //= 2
        out.append({"name": f"pool{b + 1}", "kind": "pool", "N": n,
                    "C": ch, "K": 1, "X": x, "Y": x, "R": 2, "S": 2,
                    "stride": 2, "src": [prev], "srcs": 1})
        prev = f"pool{b + 1}"
    c = c_in * x * x
    for i, k in enumerate(cfg["fc"]):
        nm = f"fc{6 + i}"
        out.append({"name": nm, "kind": "fc", "N": n, "C": c, "K": k,
                    "X": 1, "Y": 1, "src": [prev], "srcs": 1})
        prev, c = nm, k
    return out
