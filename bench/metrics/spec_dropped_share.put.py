"""Percent of the chunk bytes the fused ingest RS-encoded before dedup
that no upload then took: the window's ``SchedulerStats``
``spec_dropped_bytes`` over ``spec_encoded_bytes``. A program without
the fields, or a window that encoded nothing ahead, reads nothing."""

KIND = "put_rounds"
FIELDS = ("spec_dropped_bytes", "spec_encoded_bytes")


def read(ctx):
    d = ctx.sched_delta
    if ctx.kind != KIND or any(f not in d for f in FIELDS):
        return None
    if not d["spec_encoded_bytes"]:
        return None
    return 100.0 * d["spec_dropped_bytes"] / d["spec_encoded_bytes"]
