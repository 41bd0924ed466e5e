"""Host milliseconds per logical MiB of gets spent on read-repair hints: a
piece census of each chunk read without its k data pieces, and the check
for chunks with fewer than k (span ``sears.get.hint``): the window's
``SchedulerStats`` ``get_hint_s``. A program without the field reads
nothing."""

KIND = "open_get"
FIELDS = ("get_hint_s",)


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
