"""SHA-1 inside the fused ingest launch: work the chunk ids require.

The fused engine hashes each distinct ``(code, chunk)`` job of a window
once (intra-window duplicates share a lane).  Required: read each such
chunk's unpadded bytes once and write a 20-byte digest; the message
padding, the cap of the block axis at ``k * Lp`` bytes and the batch
padding are not counted.
"""

ENGINE_CALLS = ("hash_encode_blobs_multi",)
TRACE_OPS = ("_sha1_padded",)
PEAK_OPS = None  # bound by memory here: no VPU peak is published


def calls(method, args, kwargs):
    jobs = set(args[0] if args else kwargs["jobs"])
    return [{"message_bytes": sum(len(blob) for _, blob in jobs),
             "chunks": len(jobs)}] if jobs else []


def work(call):
    return 0.0, call["message_bytes"] + 20 * call["chunks"]
