"""Host milliseconds per logical MiB put spent waiting for kernel results,
device-to-host copy included (span ``sears.engine.wait``): the window's
``SchedulerStats`` ``engine_wait_s``. A program without the field reads
nothing."""

KIND = "put_rounds"
FIELDS = ("engine_wait_s",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
