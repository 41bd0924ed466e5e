"""Host milliseconds per logical MiB get inside the engine's data-plane
calls (packing, dispatch and the wait for the device)."""

KIND = "open_get"


def read(ctx):
    w, inst = ctx.window, ctx.instrument
    nbytes = w.put_bytes if KIND == "put_rounds" else w.get_bytes
    if inst is None or ctx.kind != KIND or not nbytes:
        return None
    return 1e3 * inst.engine_s / (nbytes / 2**20)
