"""Gear CDC kernel: work a window's chunking pass requires.

One launch per distinct chunker of a put window, over the window's files
laid end to end.  Required: read each stream byte once and write one bit
per position (the boundary candidates); the padding of the stream to its
bucket is not counted.
"""

ENGINE_CALLS = ("chunk_blobs_multi_begin",)
TRACE_OPS = ("_gear_fire_padded",)
PEAK_OPS = None  # bound by memory: no VPU peak is published


def calls(method, args, kwargs):
    jobs = args[0] if args else kwargs["jobs"]
    streams: dict = {}
    for chunker, blob in jobs:
        streams[chunker] = streams.get(chunker, 0) + len(blob)
    return [{"stream_bytes": n} for n in streams.values() if n]


def work(call):
    n = call["stream_bytes"]
    return 0.0, n + n / 8
