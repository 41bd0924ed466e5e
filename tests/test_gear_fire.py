"""The gear kernel's packed fire bitmap, issued and resolved.

``ops.gear_fire_issue`` returns one bit per stream position, packed into
uint32 words on the device (``gear_cdc._fire_kernel``), and
``ops.gear_fire_resolve`` decodes the words to the sorted positions
``chunking.gear_candidates_np`` gives on the host.  Both the jitted
oracle (``impl="ref"``) and the Pallas kernel (interpret mode off the
chip) are checked, at tile seams and over a window of uneven files.
"""

import functools

import numpy as np
import pytest

from repro.core.chunking import (GEAR_TABLE, WINDOW, Chunker,
                                 chunk_spans_batch, gear_candidates_np)
from repro.kernels import gear_cdc, ops
from repro.kernels.launches import TRANSFERS

# the hash of a position whose 32-byte window is all zero padding
_ZERO_HASH = (-int(GEAR_TABLE[0])) % (1 << 32)
# high bits that are clear in that hash: random bytes fire at 1/2^popcount,
# every position deep in the zero padding fires
TAIL_MASK = np.uint32(~_ZERO_HASH & 0xFFFF0000)
CHUNKERS = (Chunker(), Chunker(min_size=8, avg_size=64, max_size=256))
CASES = {f"n{n}": [n] for n in (1, 31, 8191, 8192, 8193, 3 * 8192 + 5)}
CASES["window"] = [5000, 1, 8192, 0, 12345, 31, 20000]  # uneven files


def _blobs(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, dtype=np.uint8) for n in lengths]


@pytest.mark.parametrize("impl", ["ref", "kernel"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_fire_resolves_to_the_host_candidates(case, impl):
    blobs = _blobs(CASES[case], seed=len(case))
    stream = np.concatenate(blobs)
    n = stream.shape[0]
    for mask in [c.mask for c in CHUNKERS] + [TAIL_MASK]:
        issued = ops.gear_fire_issue(stream, mask, impl=impl)
        words = np.asarray(issued[0])
        assert words.shape == (gear_cdc.fire_tiles(n), gear_cdc.WORDS,
                               gear_cdc.LANES)
        assert words.dtype == np.uint32
        got = ops.gear_fire_resolve(issued)
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, gear_candidates_np(stream, mask))
        if mask == TAIL_MASK and words.size * 32 - n >= WINDOW:
            # the shipped tiles flag pad positions the resolve must drop
            tail = gear_cdc.fire_positions(words, words.size * 32)
            assert (tail >= n).any()
    for chunker in CHUNKERS:
        got = chunk_spans_batch(chunker, blobs, functools.partial(
            ops.gear_candidate_positions, impl=impl))
        assert got == [chunker.chunk_spans(b) for b in blobs]


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_resolve_copies_back_one_bit_per_position(impl):
    n = 5 * gear_cdc.TILE + 100  # bucket of 8 tiles, 6 of them hold data
    stream = _blobs([n], seed=7)[0]
    issued = ops.gear_fire_issue(stream, Chunker().mask, impl=impl)
    before = TRANSFERS.snapshot()
    ops.gear_fire_resolve(issued)
    back = TRANSFERS.delta(before).d2h_bytes
    assert back == -(-n // gear_cdc.TILE) * gear_cdc.TILE // 8 == 6 * 1024
    assert ops.gear_fire_issue(np.zeros(0, np.uint8), 0, impl=impl) is None
    assert ops.gear_fire_resolve(None).shape == (0,)
