"""conv_roofline.infer: the conv layers' share of their roofline while the
device runs them, in %.

Each conv layer's least time, max(flops / ``bf16_flops_per_s``,
``min_bytes`` / ``hbm_bytes_per_s``) (``work.py``, ``peaks.json``),
summed over the conv layers and times the forwards of the traced window,
over the device seconds that window spends in ops the trace attributes
to conv layers (``trace_layers.py``: each XLA op's layer comes from
``FusedNetwork.op_layers``).  Like ``mfu.infer`` it is priced at the bf16
peak, so at float32 HIGHEST it cannot pass about a sixth of 100%.
"""
import trace_layers


def read(ctx):
    return trace_layers.kind_roofline(ctx, "conv")
