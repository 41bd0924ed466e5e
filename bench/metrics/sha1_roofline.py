"""Share of the sha1 kernel's device time that its required work needs at
the chip's peak (bench/work/sha1.py counts the work)."""


def read(ctx):
    return ctx.roofline("sha1")
