"""Share of the gf_decode kernel's device time that its required work needs at
the chip's peak (bench/work/gf_decode.py counts the work)."""


def read(ctx):
    return ctx.roofline("gf_decode")
