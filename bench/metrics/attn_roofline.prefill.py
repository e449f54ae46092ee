"""attn_roofline.prefill: the attention layers' share of their roofline
while the device runs them, in %.

Each attention layer's least time, max(flops / ``bf16_flops_per_s``,
``min_bytes`` / ``hbm_bytes_per_s``), counted by ``prefill_work.py`` for
causal attention (S(S+1)/2 position pairs, q, k and v read once, the
scores never written), summed over the attention layers that own
device time in the trace and times the traced forwards, over the device
seconds of the ops the trace attributes to those layers
(``trace_layers.reduce_scoped``: an op's layer is the ``jax.named_scope``
of its root instruction).  Layers no op carries the scope of are left
out; a traced run lists them under ``fused_away``.  Priced at the bf16
peak, at float32 HIGHEST it cannot pass about a sixth of 100%.
"""


def read(ctx):
    seconds = (ctx.get("trace") or {}).get("layers")
    work = ctx.get("layer_work")
    if not seconds or not work or not ctx.get("forwards"):
        return None
    peaks = ctx["peaks"]
    names = [n for n, w in work.items()
             if w["kind"] == "attention" and seconds.get(n, 0.0) > 0]
    busy = sum(seconds[n] for n in names)
    if busy <= 0:
        return None
    least = sum(max(work[n]["flops"] / peaks["bf16_flops_per_s"],
                    work[n]["min_bytes"] / peaks["hbm_bytes_per_s"])
                for n in names)
    return 100.0 * least * ctx["forwards"] / busy
