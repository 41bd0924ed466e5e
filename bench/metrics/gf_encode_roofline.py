"""Share of the gf_encode kernel's device time that its required work needs at
the chip's peak (bench/work/gf_encode.py counts the work)."""


def read(ctx):
    return ctx.roofline("gf_encode")
