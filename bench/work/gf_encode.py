"""GF(256) Reed-Solomon encode: work the parity of a batch requires.

A chunk of ``len`` bytes is ``k`` rows of ``L = ceil(len / k)`` bytes.
Required: read the k data rows and write the n-k parity rows; the k
identity rows are copies of the input, not work.  Bit-sliced over GF(2)
the parity is an (8(n-k), 8k) 0/1 matrix times 8k bit rows of L, i.e.
64 (n-k) k L multiply-adds, exact in bf16.  Padding of L to the tile and
of the batch to a power of two is not counted.
"""

ENGINE_CALLS = ("encode_blobs_multi",)
TRACE_OPS = ("_gf_matmul_padded",)
PEAK_OPS = "bf16_flops_per_s"


def calls(method, args, kwargs):
    jobs = args[0] if args else kwargs["jobs"]
    macs = nbytes = 0
    for code, blob in jobs:
        n, k = code.n, code.k
        L = max(1, -(-len(blob) // k))
        macs += 64 * (n - k) * k * L
        nbytes += n * L  # k rows in, n-k rows out
    return [{"macs": macs, "bytes": nbytes}] if jobs else []


def work(call):
    return 2.0 * call["macs"], call["bytes"]
