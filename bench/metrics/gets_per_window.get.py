"""Gets the scheduler coalesced into one get window, over the window."""


def read(ctx):
    d = ctx.sched_delta
    if ctx.kind != "open_get" or not d["n_get_windows"]:
        return None
    return d["n_requests"] / d["n_get_windows"]
