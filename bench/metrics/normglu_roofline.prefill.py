"""normglu_roofline.prefill: the RMSNorm and SwiGLU-product ops' share of
their roofline while the device runs them, in %.

XLA splits each RMSNorm into a row reduction, an rsqrt and a multiply it
fuses into the consumer's matmul, and fuses the SwiGLU product into the
down projection's matmul: no op does a norm's or a glu's whole work, so
a layer's algorithmic least time would overrun the ops that carry its
scope.  This share is priced per op instead: over the device ops whose
root instruction carries a norm's or a glu's ``jax.named_scope``
(``FusedNetwork.compiled_text``, ``fuse.hlo_op_layers``) and that ran in
the traced window, the bytes each reads and writes (``prefill_work
.op_bytes``: its output, and each operand whole or as the slices it
takes) at ``hbm_bytes_per_s``, times the traced forwards, over those
ops' device seconds.  These ops are memory-bound; their flops are left
out of the least time, which keeps it a lower bound.
"""


def read(ctx):
    op_layers, op_bytes = ctx.get("op_layers"), ctx.get("op_bytes")
    seconds, kinds = ctx.get("op_seconds"), ctx.get("kinds") or {}
    if not op_layers or not op_bytes or not seconds \
            or not ctx.get("forwards"):
        return None
    ops = [op for op, layer in op_layers.items()
           if kinds.get(layer) in ("norm", "glu")
           and seconds.get(op, 0.0) > 0 and op in op_bytes]
    busy = sum(seconds[op] for op in ops)
    if busy <= 0:
        return None
    least = sum(op_bytes[op] for op in ops) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * ctx["forwards"] / busy
