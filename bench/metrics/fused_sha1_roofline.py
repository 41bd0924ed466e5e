"""Share of the SHA-1 kernel's device time inside the fused ingest launch
that its required work needs at the chip's peak (bench/work/fused_sha1.py
counts the work)."""


def read(ctx):
    return ctx.roofline("fused_sha1")
