"""Host milliseconds per logical MiB of gets spent reading pieces from the
cluster nodes (span ``sears.get.read``): the window's ``SchedulerStats``
``get_read_s``. A program without the field reads nothing."""

KIND = "open_get"
FIELDS = ("get_read_s",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
