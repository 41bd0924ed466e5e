"""Host milliseconds per logical MiB put spent packing arrays for the
kernels and unpacking their results on the host (spans
``sears.engine.pack`` and ``sears.engine.unpack``): the window's
``SchedulerStats`` ``engine_pack_s`` + ``engine_unpack_s``. A program
without the field reads nothing."""

KIND = "put_rounds"
FIELDS = ("engine_pack_s", "engine_unpack_s")


def read(ctx):
    w, d = ctx.window, ctx.sched_delta
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if ctx.kind != KIND or not nbytes or any(f not in d for f in FIELDS):
        return None
    return 1e3 * sum(d[f] for f in FIELDS) / (nbytes / 2**20)
