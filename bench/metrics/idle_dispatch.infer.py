"""idle_dispatch.infer: share of the traced window in which the device
idles while the host is in ``fuse.feed`` or ``fuse.dispatch``, in %.

The device's idle intervals, shifted by the clock skew, intersected with
the program's spans on the thread that dispatches (``trace_layers.py``);
absent where the trace holds no skew.
"""
import trace_layers


def read(ctx):
    return trace_layers.idle_share(ctx, "dispatch")
