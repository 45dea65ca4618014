"""Plan nodes the executor sent to the Pallas kernels
(``Executor.kernel_dispatches``) during the window, per statement
answered in it."""


def read(ctx):
    return ctx.dispatches / len(ctx.window) if ctx.window else None
