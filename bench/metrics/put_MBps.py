"""Logical bytes of acknowledged puts (1e6 B) per second of the window."""


def read(ctx):
    w = ctx.window
    if ctx.kind != "put_rounds" or w.seconds <= 0:
        return None
    return w.put_bytes / 1e6 / w.seconds
