"""Set-up: process start until the window opens (JAX start-up, rendering
and producing the records, compile-cache loads or compiles, and the
warm-up of the cell's own shapes)."""


def read(ctx):
    return ctx.setup_s
