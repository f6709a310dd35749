"""Set-up seconds: process start to the window's start (imports, device
start-up, compile-cache loads or compilation, one warm unit)."""


def read(ctx):
    return ctx.setup_s
