"""Share of the GF encode kernel's device time inside the fused ingest
launch that its required work needs at the chip's peak
(bench/work/fused_gf_encode.py counts the work)."""


def read(ctx):
    return ctx.roofline("fused_gf_encode")
