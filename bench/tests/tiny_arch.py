"""A net small enough for the CPU, with each kind of layer the cells run:
a strided conv stem, a zero-padded max pool, one bottleneck block with a
projection and an add, a global pool and an fc.  The layer list the
reference runs, and (``program_graph``) the same net built with the
program's layer builders, as a configuration's builder would."""
from __future__ import annotations

CONFIG = {"name": "tiny", "builder": "bench_tiny", "arch": "tiny_arch.py",
          "conv_fc_macs": {"4": 316032}}


def _l(name, kind, n, c, k, x, r=None, stride=None, src=()):
    d = {"name": name, "kind": kind, "N": n, "C": c, "K": k, "X": x,
         "Y": x, "src": list(src), "srcs": len(src)}
    if r is not None:
        d.update(R=r, S=r, stride=stride)
    return d


def layers(cfg, batch):
    n = batch
    return [
        _l("conv1", "conv", n, 3, 8, 16, 3, 2),
        _l("pool1", "pool", n, 8, 1, 8, 3, 2, ["conv1"]),
        _l("b.a", "conv", n, 8, 4, 8, 1, 1, ["pool1"]),
        _l("b.b", "conv", n, 4, 4, 8, 3, 1, ["b.a"]),
        _l("b.c", "conv", n, 4, 16, 8, 1, 1, ["b.b"]),
        _l("b.p", "conv", n, 8, 16, 8, 1, 1, ["pool1"]),
        _l("b.add", "eltwise", n, 16, 1, 8, src=["b.c", "b.p"]),
        _l("gap", "pool", n, 16, 1, 1, 8, 8, ["b.add"]),
        _l("fc", "fc", n, 16, 10, 1, src=["gap"]),
    ]


def program_graph(batch):
    from repro.workloads.layers import LayerGraph, conv, eltwise, fc, pool
    n = batch
    return LayerGraph("tiny", [
        conv("conv1", n, 3, 8, 16, 16, 3, 3, stride=2),
        pool("pool1", n, 8, 8, 8, 3, 3, src=["conv1"]),
        conv("b.a", n, 8, 4, 8, 8, 1, 1, src=["pool1"]),
        conv("b.b", n, 4, 4, 8, 8, 3, 3, src=["b.a"]),
        conv("b.c", n, 4, 16, 8, 8, 1, 1, src=["b.b"]),
        conv("b.p", n, 8, 16, 8, 8, 1, 1, src=["pool1"]),
        eltwise("b.add", n, 16, 8, 8, src=["b.c", "b.p"]),
        pool("gap", n, 16, 1, 1, 8, 8, stride=8, src=["b.add"]),
        fc("fc", n, 16, 10, src=["gap"]),
    ])
