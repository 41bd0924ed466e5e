"""SHA-1 kernel: work the chunk ids of a batch require.

Required: read each chunk's unpadded bytes once and write a 20-byte
digest per chunk; the message padding and the batch padding are not
counted.
"""

ENGINE_CALLS = ("hash_chunks",)
TRACE_OPS = ("_sha1_padded",)
PEAK_OPS = None  # bound by memory here: no VPU peak is published


def calls(method, args, kwargs):
    chunks = args[0] if args else kwargs["chunks"]
    return [{"message_bytes": sum(len(c) for c in chunks),
             "chunks": len(chunks)}] if chunks else []


def work(call):
    return 0.0, call["message_bytes"] + 20 * call["chunks"]
