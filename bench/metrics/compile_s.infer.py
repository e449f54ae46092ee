"""compile_s.infer: seconds the first call of the fused serving
executable took beyond a steady call, in set-up: compiling it, or loading
it from the persistent cache."""


def read(ctx):
    return ctx.get("compile_s")
