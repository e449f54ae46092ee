"""solve_s.prefill: host seconds of ``LocalClient.solve`` in set-up: the
KAPLA solve of the whole looped graph over a fresh schedule store."""


def read(ctx):
    return ctx.get("solve_s")
