"""mfu.prefill: the fused executable's share of the chip's bf16 peak while
the device runs a prefill, in %.

2 x the MACs a forward needs (``prefill_work.py``: the fc layers and
causal attention over the positions each query sees) x the forwards of
the traced window, over the device's busy seconds in that window (the
profiler trace, ``trace_reduce.py``) times ``bf16_flops_per_s`` of
``peaks.json``.  Host gaps do not enter it.  The program forms the whole
S x S score matrix, and at float32 HIGHEST (six bf16 passes a product)
it cannot pass about a sixth of 100%.
"""


def read(ctx):
    tr = ctx.get("trace")
    if not ctx.get("forwards") or not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * 2 * ctx["macs_per_forward"] * ctx["forwards"] \
        / tr["busy_s"] / ctx["peaks"]["bf16_flops_per_s"]
