"""Share of the gear kernel's device time that its required work needs at
the chip's peak (bench/work/gear.py counts the work)."""


def read(ctx):
    return ctx.roofline("gear")
