"""Host milliseconds per logical MiB put that the fused ingest spends on
the GF rows of its speculative encode: filling the (B, k, L) rows of
every distinct chunk before dedup and cutting their parity into pieces
(span ``sears.engine.spec_encode``, inside ``sears.engine.pack`` and
``.unpack``): the window's ``SchedulerStats`` ``engine_spec_s``.  With
``spec_dropped_share.put`` it gives the host time speculation wastes.
A program without the field, or a window that encoded nothing ahead (a
staged engine), reads nothing."""

KIND = "put_rounds"
FIELDS = ("engine_spec_s", "spec_encoded_bytes")


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    if ctx.kind != KIND or not w.put_bytes or any(f not in d for f in FIELDS):
        return None
    if not d["spec_encoded_bytes"]:
        return None
    return 1e3 * d["engine_spec_s"] / (w.put_bytes / 2**20)
