"""Bytes the program copied from device to host per logical byte put,
padding included: the window's ``SchedulerStats.d2h_bytes``. A program
without the field reads nothing."""

KIND = "put_rounds"
FIELDS = ("d2h_bytes",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return sum(d[f] for f in FIELDS) / nbytes
