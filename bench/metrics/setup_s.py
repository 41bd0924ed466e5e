"""Seconds from process start to the window: JAX start-up, store, data,
prefill and warm-up (compiles or compile-cache loads included)."""


def read(ctx):
    return ctx.setup_s
