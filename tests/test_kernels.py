"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body itself executes);
oracles are the ``ref.py`` functions, themselves pinned to independent
host references (python GF tables, sequential gear hash, hashlib).

Interpret mode executes the Pallas kernel bodies in Python, so those
sweeps take minutes on CPU: they are marked ``@pytest.mark.slow`` and
deselected from the default tier-1 run (see pytest.ini; run them with
``make test-slow``).  The ref-oracle-vs-host pins stay in tier-1 so the
kernels' semantic contracts remain covered by the fast lane.
"""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashing
from repro.core.chunking import gear_hash_sequential
from repro.core.rs_code import RSCode, decode_matrix, generator_matrix
from repro.kernels import ops, ref


@pytest.mark.slow
# ------------------------------------------------------------ gf_matmul ----
@pytest.mark.parametrize("n,k", [(10, 5), (6, 4), (4, 2), (10, 9), (3, 1)])
@pytest.mark.parametrize("B,L", [(1, 64), (3, 512), (2, 1000), (1, 4096)])
def test_gf_matmul_kernel_vs_ref(n, k, B, L):
    rng = np.random.RandomState(n * 100 + k + B + L)
    G = generator_matrix(n, k)
    data = rng.randint(0, 256, size=(B, k, L), dtype=np.uint8)  # noqa: NPY002
    out_k = np.asarray(ops.rs_apply(G, data, impl="kernel"))
    out_r = np.asarray(ops.rs_apply(G, data, impl="ref"))
    np.testing.assert_array_equal(out_k, out_r)
    assert out_k.dtype == np.uint8 and out_k.shape == (B, n, L)


def test_gf_matmul_ref_vs_host_numpy():
    rng = np.random.RandomState(0)
    code = RSCode(10, 5)
    data = rng.randint(0, 256, size=(5, 128), dtype=np.uint8)  # noqa: NPY002
    host = code.encode(data)
    dev = np.asarray(ops.rs_apply(generator_matrix(10, 5), data[None],
                                  impl="ref"))[0]
    np.testing.assert_array_equal(host, dev)


@pytest.mark.slow
def test_gf_matmul_encode_decode_roundtrip_kernel():
    rng = np.random.RandomState(1)
    code = RSCode(10, 5)
    data = rng.randint(0, 256, size=(4, 5, 300), dtype=np.uint8)  # noqa: NPY002
    pieces = np.asarray(ops.rs_encode(code, data))
    idx = (1, 3, 5, 7, 9)
    rec = np.asarray(ops.rs_decode(code, pieces[:, list(idx)], idx))
    np.testing.assert_array_equal(rec, data)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(0, 10**6))
def test_gf_matmul_property_random_matrices(k, seed):
    rng = np.random.RandomState(seed % 2**31)
    r = int(rng.randint(1, 12))
    M = rng.randint(0, 256, size=(r, k), dtype=np.uint8)  # noqa: NPY002
    data = rng.randint(0, 256, size=(2, k, 96), dtype=np.uint8)  # noqa: NPY002
    np.testing.assert_array_equal(
        np.asarray(ops.rs_apply(M, data, impl="kernel")),
        np.asarray(ops.rs_apply(M, data, impl="ref")))


@pytest.mark.slow
# ------------------------------------------------------------- gear_cdc ----
@pytest.mark.parametrize("n", [1, 31, 32, 100, 8192, 8193, 20000])
def test_gear_kernel_vs_ref(n):
    rng = np.random.RandomState(n)
    data = rng.randint(0, 256, size=n, dtype=np.uint8)  # noqa: NPY002
    out_k = np.asarray(ops.gear_hash(data, impl="kernel"))
    out_r = np.asarray(ops.gear_hash(data, impl="ref"))
    np.testing.assert_array_equal(out_k, out_r)


def test_gear_ref_vs_sequential_oracle():
    rng = np.random.RandomState(5)
    data = rng.randint(0, 256, size=3000, dtype=np.uint8)  # noqa: NPY002
    np.testing.assert_array_equal(np.asarray(ref.gear_hash_ref(data)),
                                  gear_hash_sequential(data))


@pytest.mark.parametrize("n", [2 * 8192 + 100, 3 * 8192])
def test_gear_kernel_pinned_to_refs(n):
    """The compiled-path gear body (lane-gather lookup, static taps) in
    interpret mode equals the jnp oracle and the host candidate scan over
    2-3 tiles: first-tile zero halo, tile seams, and bucket padding."""
    from repro.core.chunking import gear_candidates_np
    from repro.kernels import gear_cdc

    data = np.random.RandomState(n).randint(  # noqa: NPY002
        0, 256, size=n, dtype=np.uint8)
    h = np.asarray(gear_cdc.gear_hash(data, interpret=True))
    np.testing.assert_array_equal(h, np.asarray(ref.gear_hash_ref(data)))
    np.testing.assert_array_equal(h[:40], gear_hash_sequential(data[:40]))
    mask = np.uint32((1 << 9) - 1)
    words = np.asarray(gear_cdc.gear_fire(data, mask, interpret=True))
    assert words.shape == (gear_cdc.fire_tiles(n), gear_cdc.WORDS,
                           gear_cdc.LANES)
    np.testing.assert_array_equal(gear_cdc.fire_positions(words, n),
                                  gear_candidates_np(data, mask))


@pytest.mark.slow
def test_gear_kernel_tile_boundary_exactness():
    # values spanning the 8192-byte tile boundary depend on the halo
    rng = np.random.RandomState(6)
    data = rng.randint(0, 256, size=3 * 8192, dtype=np.uint8)  # noqa: NPY002
    out = np.asarray(ops.gear_hash(data, impl="kernel"))
    seq = gear_hash_sequential(data)
    np.testing.assert_array_equal(out[8190:8200], seq[8190:8200])
    np.testing.assert_array_equal(out, seq)


@pytest.mark.slow
# ----------------------------------------------------------------- sha1 ----
@pytest.mark.parametrize("sizes", [
    [0], [1], [55], [56], [64], [119], [200, 3, 64, 0, 1000],
    list(range(0, 150, 7)),
])
def test_sha1_kernel_vs_hashlib(sizes):
    rng = np.random.RandomState(sum(sizes) + len(sizes))
    chunks = [rng.randint(0, 256, size=s, dtype=np.uint8).tobytes()  # noqa: NPY002
              for s in sizes]
    got = ops.sha1_digests(chunks, impl="kernel")
    want = [hashlib.sha1(c).digest() for c in chunks]
    assert got == want


def test_sha1_ref_vs_hashlib_batch():
    rng = np.random.RandomState(9)
    chunks = [rng.randint(0, 256, size=s, dtype=np.uint8).tobytes()  # noqa: NPY002
              for s in (0, 10, 63, 64, 65, 500, 8192)]
    blocks, counts = hashing.sha1_pad_batch(chunks)
    words = np.asarray(ref.sha1_ref(blocks, counts))
    got = hashing.digest_words_to_bytes(words)
    assert got == [hashlib.sha1(c).digest() for c in chunks]


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=400), min_size=1, max_size=6))
def test_sha1_kernel_property(chunks):
    got = ops.sha1_digests(chunks, impl="kernel")
    assert got == [hashlib.sha1(c).digest() for c in chunks]


@pytest.mark.slow
def test_sha1_large_batch_crosses_tile():
    chunks = [bytes([i % 256]) * (i % 300) for i in range(300)]  # > TILE_B
    got = ops.sha1_digests(chunks, impl="kernel")
    assert got == [hashlib.sha1(c).digest() for c in chunks]


@pytest.mark.slow
# ------------------------------------------------- end-to-end kernel path --
def test_store_with_device_hash_path():
    """SEARSStore using the batched device SHA-1 for chunk ids."""
    from repro.core.store import SEARSStore

    def device_hash(data: bytes) -> bytes:
        return ops.sha1_digests([data], impl="ref")[0]

    s = SEARSStore(num_clusters=2, node_capacity=32 << 20,
                   hash_fn=device_hash)
    blob = np.random.RandomState(7).randint(  # noqa: NPY002
        0, 256, size=50_000, dtype=np.uint8).tobytes()
    s.put_file("u", "f", blob)
    out, _ = s.get_file("u", "f")
    assert out == blob
