"""GF(256) Reed-Solomon encode inside the fused ingest launch: work the
parity of a window requires.

The fused engine encodes each distinct ``(code, chunk)`` job of a window
once, before dedup decides which chunks are new; every such encode is
counted, since the kernel computes it (``spec_dropped_share.put`` reads
how much of it dedup throws away).  A chunk of ``len`` bytes is ``k``
rows of ``L = ceil(len / k)`` bytes.  Required: read the k data rows and
write the n-k parity rows; bit-sliced over GF(2) that is 64 (n-k) k L
multiply-adds, exact in bf16.  Padding of L to the tile and of the batch
to a power of two is not counted.
"""

ENGINE_CALLS = ("hash_encode_blobs_multi",)
TRACE_OPS = ("_gf_matmul_padded",)
PEAK_OPS = "bf16_flops_per_s"


def calls(method, args, kwargs):
    jobs = set(args[0] if args else kwargs["jobs"])
    macs = nbytes = 0
    for code, blob in jobs:
        n, k = code.n, code.k
        L = max(1, -(-len(blob) // k))
        macs += 64 * (n - k) * k * L
        nbytes += n * L  # k rows in, n-k rows out
    return [{"macs": macs, "bytes": nbytes}] if jobs else []


def work(call):
    return 2.0 * call["macs"], call["bytes"]
