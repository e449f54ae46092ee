"""lower_s.infer: host seconds of ``lower_network`` in set-up."""


def read(ctx):
    return ctx.get("lower_s")
