"""Process start to window start: generation, sort, index build, save,
open, JAX start-up and warm-up."""


def read(ctx):
    return ctx.setup_s
