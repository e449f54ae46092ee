"""idle_wait.infer: share of the traced window in which the device idles
while the host is in ``netexec.wait`` (the ``block_until_ready`` loop over
the outputs), in %.

Output handling and stalls inside the executable fall here.  Computed
as ``idle_dispatch.infer`` (``trace_layers.py``).
"""
import trace_layers


def read(ctx):
    return trace_layers.idle_share(ctx, "wait")
