"""pool_roofline.infer: the pool layers' share of their roofline while the
device runs them, in %.

As ``conv_roofline.infer``, over the pool layers: each reads its input
window once and writes its output once (``work.py`` ``min_bytes``), so
their least time is the bytes at HBM bandwidth.
"""
import trace_layers


def read(ctx):
    return trace_layers.kind_roofline(ctx, "pool")
