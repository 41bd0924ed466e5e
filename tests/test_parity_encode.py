"""Parity-only encode: the batched paths apply only the (n-k, k) parity
block, copy back only the parity pieces, and cut the k data pieces from
each blob's own bytes, byte-identical to ``encode_bytes``."""

import hashlib

import numpy as np
import pytest

from repro.core.engine import FusedEngine, KernelEngine
from repro.core.rs_code import RSCode, data_pieces, padded_piece_len
from repro.kernels import ops
from repro.kernels.gf_matmul import TILE_L
from repro.kernels.launches import LAUNCHES, TRACES, TRANSFERS

# the two preset codes with their classes' chunk_max
CODES = {"10_5": (RSCode(10, 5), 8192), "14_10": (RSCode(14, 10), 16384)}


def _blobs(code, chunk_max, seed=0):
    k = code.k
    sizes = (1, k - 1, k * 512 - 1, k * 512, k * 512 + 1, k * 1024 + 1,
             chunk_max)
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, np.uint8).tobytes() for n in sizes]


def _kernel(impl):
    def run(code, blobs):
        return KernelEngine(impl=impl).encode_blobs_multi(
            [(code, b) for b in blobs])
    return run


def _fused(impl):
    def run(code, blobs):
        ids, pieces = FusedEngine(impl=impl).hash_encode_blobs_multi(
            [(code, b) for b in blobs])
        assert ids == [hashlib.sha1(b).digest() for b in blobs]
        return pieces
    return run


PATHS = {
    "kernel_ref": _kernel("ref"),
    "kernel_interpret": _kernel("kernel"),  # Pallas in interpret mode
    "numpy": lambda code, blobs: code.encode_blobs(blobs, quantum=TILE_L),
    "fused_ref": _fused("ref"),
    "fused_interpret": _fused("kernel"),
}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("name", sorted(CODES))
def test_parity_only_encode_matches_encode_bytes(name, path):
    code, chunk_max = CODES[name]
    blobs = _blobs(code, chunk_max)
    buckets = {padded_piece_len(code.piece_len(len(b)), TILE_L)
               for b in blobs}
    assert buckets == {512, 1024, 1536, 2048}
    got = PATHS[path](code, blobs)
    for blob, pieces in zip(blobs, got):
        want = code.encode_bytes(blob)
        assert len(pieces) == code.n
        for j in range(code.n):
            assert pieces[j] == want[j], (len(blob), j)


@pytest.mark.parametrize("name", sorted(CODES))
def test_data_pieces_are_the_identity_rows(name):
    code, chunk_max = CODES[name]
    for blob in _blobs(code, chunk_max, seed=1) + [b""]:
        L = code.piece_len(len(blob))
        assert data_pieces(blob, code.k, L) == code.encode_bytes(blob)[
            :code.k]


@pytest.mark.parametrize("impl", ["ref", "kernel"])
@pytest.mark.parametrize("name", sorted(CODES))
def test_encode_batch_copies_back_only_parity(name, impl):
    code, chunk_max = CODES[name]
    blobs = _blobs(code, chunk_max, seed=2)
    lens = [padded_piece_len(code.piece_len(len(b)), TILE_L)
            for b in blobs]
    before = TRANSFERS.snapshot()
    ops.rs_encode_blobs(code, blobs, impl=impl)
    moved = TRANSFERS.delta(before)
    back = there = 0
    for Lp in set(lens):
        Bp = ops._pow2(lens.count(Lp))
        back += Bp * (code.n - code.k) * Lp
        there += Bp * code.k * Lp
    assert moved.d2h_bytes == back
    assert moved.h2d_bytes == there


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_second_batch_in_the_same_bucket_adds_no_trace(impl):
    code, _ = CODES["10_5"]
    rng = np.random.default_rng(4)

    def blobs(count):  # one piece-length bucket (Lp = 1024)
        return [rng.integers(0, 256, 4000 + 7 * i, np.uint8).tobytes()
                for i in range(count)]

    first = blobs(5)
    ops.rs_encode_blobs(code, first, impl=impl)  # batch padded to 8
    traces, launches = TRACES.snapshot(), LAUNCHES.snapshot()
    second = blobs(7)  # padded to 8 as well
    got = ops.rs_encode_blobs(code, second, impl=impl)
    assert TRACES.delta(traces).gf == 0
    assert LAUNCHES.delta(launches).gf == 1
    assert got == [code.encode_bytes(b) for b in second]
