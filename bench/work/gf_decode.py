"""GF(256) Reed-Solomon decode: work a degraded read requires.

A read takes the first k live pieces of a chunk (rows of
``L = ceil(len / k)`` bytes).  Required: read those k rows and write the
data rows that were not among them; a chunk whose k data rows all arrived
needs no decode.  Bit-sliced: 64 m k L multiply-adds for m missing data
rows, exact in bf16.  Padding is not counted.
"""

ENGINE_CALLS = ("decode_blobs_multi", "decode_blobs_multi_begin")
TRACE_OPS = ("_gf_matmul_padded",)
PEAK_OPS = "bf16_flops_per_s"


def calls(method, args, kwargs):
    jobs = args[0] if args else kwargs["jobs"]
    macs = nbytes = 0
    for code, pieces, length in jobs:
        k = code.k
        used = sorted(pieces)[:k]
        missing = k - sum(1 for j in used if j < k)
        if not missing:
            continue
        L = max(1, -(-length // k))
        macs += 64 * missing * k * L
        nbytes += (k + missing) * L
    return [{"macs": macs, "bytes": nbytes}] if macs else []


def work(call):
    return 2.0 * call["macs"], call["bytes"]
