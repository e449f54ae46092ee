"""mfu.infer: the fused executable's share of the chip's bf16 peak while
the device runs it, in %.

2 x conv+fc MACs (counted by ``work.py`` from the layer shapes) x the
forwards of the traced window, over the device's busy seconds in that
window (the profiler trace, ``trace_reduce.py``) times
``bf16_flops_per_s`` of ``peaks.json``.  Host gaps between calls do not
enter it; it reads the same whatever implements the layers, and at
float32 HIGHEST (six bf16 passes a product) it cannot pass about a sixth
of 100%.
"""


def read(ctx):
    tr = ctx.get("trace")
    if not ctx.get("forwards") or not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * 2 * ctx["macs_per_forward"] * ctx["forwards"] \
        / tr["busy_s"] / ctx["peaks"]["bf16_flops_per_s"]
