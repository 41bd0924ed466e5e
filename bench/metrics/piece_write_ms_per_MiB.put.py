"""Host milliseconds per logical MiB put spent writing the encoded pieces
to the cluster nodes (span ``sears.put.write``): the window's
``SchedulerStats`` ``put_write_s``. A program without the field reads
nothing."""

KIND = "put_rounds"
FIELDS = ("put_write_s",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
