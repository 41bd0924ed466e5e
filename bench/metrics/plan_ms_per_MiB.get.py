"""Host milliseconds per logical MiB of gets spent planning the gets:
metadata and index lookups (span ``sears.get.plan``): the window's
``SchedulerStats`` ``get_plan_s``. A program without the field reads
nothing."""

KIND = "open_get"
FIELDS = ("get_plan_s",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
