"""XLA compilations inside the measured window (`JitCompileCollector`)."""


def read(ctx):
    return ctx.get("compiles_in_window")
