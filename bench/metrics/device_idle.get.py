"""Percent of the traced window in which no operation ran on the device."""

KIND = "open_get"


def read(ctx):
    r = ctx.reduction
    if r is None or ctx.kind != KIND or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
