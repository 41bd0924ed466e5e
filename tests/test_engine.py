"""Differential tests for the data-plane engine seam.

The contract: ``NumpyEngine`` (per-chunk host path) and ``KernelEngine``
(length-bucketed Pallas batches) are byte-identical, so every store-level
artifact -- reconstructed files, piece placement, piece bytes on nodes,
dedup ratio, StoreStats -- is engine-invariant.
"""

import hashlib

import numpy as np
import pytest

from repro.core.engine import (FusedEngine, KernelEngine, NumpyEngine,
                               make_engine)
from repro.core.rs_code import RSCode
from repro.core.store import SEARSStore
from repro.kernels import ops


def _data(n, seed=0):
    return np.random.RandomState(seed).randint(  # noqa: NPY002
        0, 256, size=n, dtype=np.uint8).tobytes()


def _store(engine, **kw):
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    return SEARSStore(n=10, k=5, binding="ulb", engine=engine, **kw)


def _workload():
    """Multi-file, duplicate-heavy, length-diverse workload."""
    base = [_data(9_000 + 4561 * i, seed=40 + i) for i in range(5)]
    files = [(f"f{i}", b) for i, b in enumerate(base)]
    files.append(("dup-exact", base[1]))            # whole-file duplicate
    files.append(("dup-concat", base[0] + base[2]))  # shared-chunk prefix
    files.append(("tiny", b"x"))
    files.append(("empty", b""))
    return files


# ------------------------------------------------------------ unit level ---
def test_rs_encode_blobs_matches_per_blob():
    code = RSCode(10, 5)
    rng = np.random.RandomState(1)  # noqa: NPY002
    blobs = [bytes(rng.randint(0, 256, size=n, dtype=np.uint8))
             for n in (1, 5, 64, 813, 4096, 5000, 8192)]
    batched = ops.rs_encode_blobs(code, blobs, impl="kernel")
    for blob, pieces in zip(blobs, batched):
        assert pieces == code.encode_bytes(blob)


@pytest.mark.parametrize("indices", [
    (0, 1, 2, 3, 4),          # systematic fast path
    (1, 2, 3, 4, 5),          # one parity piece
    (5, 6, 7, 8, 9),          # all parity
    (0, 2, 4, 6, 8),          # mixed
])
def test_rs_decode_blobs_matches_per_blob(indices):
    code = RSCode(10, 5)
    rng = np.random.RandomState(2)  # noqa: NPY002
    jobs = []
    want = []
    for n in (3, 700, 813, 4096, 6000):
        blob = bytes(rng.randint(0, 256, size=n, dtype=np.uint8))
        pieces = code.encode_bytes(blob)
        jobs.append(({i: pieces[i] for i in indices}, n))
        want.append(blob)
    got = ops.rs_decode_blobs(code, jobs, impl="kernel")
    assert got == want
    assert code.decode_blobs(jobs) == want  # numpy batch API agrees


def test_rs_decode_blobs_insufficient_pieces_raises():
    code = RSCode(10, 5)
    blob = _data(1000, seed=3)
    pieces = code.encode_bytes(blob)
    with pytest.raises(ValueError):
        ops.rs_decode_blobs(code, [({0: pieces[0]}, 1000)])


def test_kernel_engine_hashes_match_hashlib():
    eng = KernelEngine(hash_batch=64)
    chunks = [_data(n, seed=n) for n in (0, 1, 55, 64, 1000, 4096, 8192)]
    assert eng.hash_chunks(chunks) == [
        hashlib.sha1(c).digest() for c in chunks]


def test_kernel_engine_hash_launch_shapes_stay_bucketed(monkeypatch):
    """Oversized chunks must not widen the compiled (B, M, 16) launch.

    The engine docstring promises a bounded compiled-shape set: every
    SHA-1 launch pads both axes to the next power of two (block axis
    clamped to blocks(max_hash_len)), so small windows stop paying the
    worst-case width.  A chunk longer than ``max_hash_len`` used to
    silently grow the block axis (``sha1_pad_batch`` took ``max`` of the
    cap and the batch's own need); now it raises instead of launching a
    wider shape or being hashed on the host.
    """
    from repro.kernels import ops

    eng = KernelEngine(hash_batch=8, max_hash_len=1024)
    fixed_blocks = (1024 + 9 + 63) // 64  # 17; pow2(17) clamps back to 17
    seen_shapes = []
    real = ops.sha1_digest_flat

    def spy(head, rest, offsets, lengths, n_blocks, impl="kernel"):
        seen_shapes.append((len(offsets), n_blocks, 16))
        return real(head, rest, offsets, lengths, n_blocks, impl=impl)

    monkeypatch.setattr(ops, "sha1_digest_flat", spy)
    chunks = [_data(100, seed=1), _data(1024, seed=3), _data(0, seed=4)]
    digests = eng.hash_chunks(chunks)
    assert digests == [hashlib.sha1(c).digest() for c in chunks]
    # one launch: 3 in-cap chunks pad to batch 4 (pow2), 17 blocks (cap)
    assert seen_shapes == [(4, fixed_blocks, 16)]
    with pytest.raises(ValueError, match="max_len"):  # 5000 > max_hash_len
        eng.hash_chunks(chunks + [_data(5000, seed=2)])
    assert seen_shapes == [(4, fixed_blocks, 16)]  # nothing launched


# ----------------------------------------- SHA-1 padding on the device ---
# The staged engine ships each batch's chunk bytes once, in 512-byte rows
# with offsets and lengths, and pads them on the device (sha1.message_blocks)

MAX_HASH = 8192
_EDGES = (0, 1, 55, 56, 63, 64, 119, 120, MAX_HASH - 9, MAX_HASH - 8,
          MAX_HASH)
# one row-sized max chunk: every lane fills its whole window of rows
_FULL = 129 * 64 - 9


def _edge_case(k, batch):
    return MAX_HASH, [_EDGES[(k + j) % len(_EDGES)] for j in range(batch)]


_FLAT_CASES = {f"len{n}-batch{b}": _edge_case(k, b)
               for k, n in enumerate(_EDGES) for b in (1, 3, 128, 512)}
# the last chunk ends on the head's last row; the lanes after it read
# their windows from the zeros the device holds
_FLAT_CASES["head-edge"] = (MAX_HASH, [4096] + [MAX_HASH] * 322 + [0] * 189)
# every lane at its longest: the last lane's window ends on the buffer's
# last row (one row short, a clamped gather would repeat a row)
_FLAT_CASES["full"] = (_FULL, [_FULL] * 512)


@pytest.mark.parametrize("case", sorted(_FLAT_CASES))
def test_staged_sha1_pads_on_the_device_like_hashlib(case):
    from repro.core import hashing

    max_len, lengths = _FLAT_CASES[case]
    rng = np.random.default_rng(len(case))
    chunks = [rng.integers(0, 256, n, np.uint8).tobytes() for n in lengths]
    if case in ("head-edge", "full"):  # the layout the case is named for
        head, rest, offsets, _, n_blocks = hashing.sha1_pack(
            chunks, 512, max_len)
        rows = hashing.sha1_flat_rows(512, n_blocks)[1]
        assert (rest is None and offsets[323] == head.nbytes
                if case == "head-edge" else
                offsets[-1] // 512 + -(-n_blocks // 8) == rows)
    eng = KernelEngine(impl="ref", max_hash_len=max_len)
    assert eng.hash_chunks(chunks) == [
        hashlib.sha1(c).digest() for c in chunks]


def test_staged_sha1_chunk_past_the_cap_raises():
    # the cap is blocks(max_hash_len): 129 blocks hold up to _FULL bytes
    eng = KernelEngine(impl="ref", max_hash_len=MAX_HASH)
    assert eng.hash_chunks([_data(_FULL)]) == [
        hashlib.sha1(_data(_FULL)).digest()]
    with pytest.raises(ValueError, match="max_len"):
        eng.hash_chunks([_data(10), _data(_FULL + 1, seed=1)])


def _class_chunks(cmin, cavg, cmax, n, seed):
    """The first ``n`` gear-CDC chunks of random bytes, one class's sizes."""
    from repro.core.chunking import Chunker

    data = np.random.default_rng(seed).integers(
        0, 256, n * cmax, np.uint8).tobytes()
    spans = Chunker(min_size=cmin, avg_size=cavg, max_size=cmax).chunk_spans(
        data)
    assert len(spans) > n
    return [data[o:o + ln] for o, ln in spans[:n]]


@pytest.mark.parametrize("cls", [(1024, 4096, 8192), (2048, 8192, 16384)],
                         ids=["realtime", "archival"])
def test_staged_sha1_ships_a_batch_of_chunks_about_once(cls):
    from repro.kernels.launches import TRANSFERS

    chunks = _class_chunks(*cls, 512, seed=cls[0])
    eng = KernelEngine(impl="ref", max_hash_len=cls[2])
    before = TRANSFERS.snapshot()
    assert eng.hash_chunks(chunks) == [
        hashlib.sha1(c).digest() for c in chunks]
    # host-padded, the batch shipped 512 x 16 B per block of the longest
    # chunk: about twice its bytes
    moved = TRANSFERS.delta(before).h2d_bytes
    assert moved <= 1.25 * sum(map(len, chunks)) + 8 * 512


def test_staged_sha1_traces_stay_bounded_over_random_batches(monkeypatch):
    from repro.kernels.launches import TRACES

    eng = KernelEngine(impl="ref", max_hash_len=1024)
    shapes = set()
    real = ops.sha1_digest_flat

    def spy(head, rest, offsets, lengths, n_blocks, impl="kernel"):
        shapes.add((len(offsets), n_blocks, len(head),
                    None if rest is None else len(rest)))
        return real(head, rest, offsets, lengths, n_blocks, impl=impl)

    monkeypatch.setattr(ops, "sha1_digest_flat", spy)
    rng = np.random.default_rng(11)
    before = TRACES.sha1
    for _ in range(50):  # one class: 128 B min, 512 B mean, 1 KiB max
        lengths = np.minimum(128 + rng.geometric(1 / 384, 512), 1024)
        chunks = [rng.integers(0, 256, n, np.uint8).tobytes()
                  for n in lengths]
        assert eng.hash_chunks(chunks) == [
            hashlib.sha1(c).digest() for c in chunks]
    # 512 lanes, a block axis of 16 or 17: byte counts never add a shape
    assert {s[:2] for s in shapes} <= {(512, 16), (512, 17)}
    assert TRACES.sha1 - before <= 2


def test_staged_sha1_custom_hash_fn_stays_on_the_host():
    from repro.core import hashing
    from repro.kernels.launches import LAUNCHES

    eng = KernelEngine(hash_fn=hashing.fast_chunk_id, impl="ref")
    chunks = [_data(n, seed=n) for n in (0, 64, 5000)]
    before = LAUNCHES.sha1
    assert eng.hash_chunks(chunks) == [
        hashing.fast_chunk_id(c) for c in chunks]
    assert LAUNCHES.sha1 == before


def test_sha1_pad_batch_max_len_is_authoritative():
    """The cap bounds the block axis; under it, widths bucket to pow2."""
    from repro.core import hashing

    blocks, counts = hashing.sha1_pad_batch([b"x" * 10], max_len=1024)
    assert blocks.shape == (1, 1, 16)  # 1-block need stays 1, not cap=17
    blocks, _ = hashing.sha1_pad_batch([b"x" * 200], max_len=1024)
    assert blocks.shape == (1, 4, 16)  # 4-block need: pow2 bucket
    blocks, _ = hashing.sha1_pad_batch([b"x" * 1024], max_len=1024)
    assert blocks.shape == (1, 17, 16)  # pow2(17)=32 clamps to the cap
    with pytest.raises(ValueError, match="max_len"):
        hashing.sha1_pad_batch([b"x" * 5000], max_len=1024)


def test_make_engine_specs():
    assert isinstance(make_engine("numpy"), NumpyEngine)
    assert isinstance(make_engine("kernel"), KernelEngine)
    fused = make_engine("fused")
    assert isinstance(fused, FusedEngine)
    assert fused.supports_fused_ingest
    eng = NumpyEngine()
    assert make_engine(eng) is eng
    with pytest.raises(ValueError):
        make_engine("vax")
    # the SHA-1 cap follows the classes; a given engine may not be short
    assert make_engine("kernel", max_hash_len=16384).max_hash_len == 16384
    with pytest.raises(ValueError, match="16384"):
        make_engine(KernelEngine(), max_hash_len=16384)


# ------------------------------------------------------- differential ------
def test_engines_differential_roundtrip():
    """Same workload through both engines: identical bytes, stats, pieces.

    Uploads go per-file through the numpy store and batched through the
    kernel store, so the test also proves put_files == sequential put_file.
    """
    files = _workload()
    s_np = _store("numpy", seed=7)
    s_kn = _store("kernel", seed=7)

    up_np = [s_np.put_file("u", fn, b) for fn, b in files]
    up_kn = s_kn.put_files("u", files)
    assert up_np == up_kn

    # identical StoreStats (=> identical dedup_ratio) and placement
    assert s_np.stats() == s_kn.stats()
    assert s_np.stats().dedup_ratio == s_kn.stats().dedup_ratio
    t_np, t_kn = s_np.switching["u"].table, s_kn.switching["u"].table
    assert set(t_np) == set(t_kn)
    for fn in t_np:
        assert t_np[fn].entries == t_kn[fn].entries  # same chunks+clusters
    for c_np, c_kn in zip(s_np.clusters, s_kn.clusters):
        for n_np, n_kn in zip(c_np.nodes, c_kn.nodes):
            assert n_np._pieces == n_kn._pieces  # stored bytes identical

    # healthy retrieval: identical bytes and stats
    names = [fn for fn, _ in files]
    got_np = [s_np.get_file("u", fn) for fn in names]
    got_kn = s_kn.get_files("u", names)
    for (fn, b), (o1, st1), (o2, st2) in zip(files, got_np, got_kn):
        assert o1 == b and o2 == b
        assert (st1.n_fetched, st1.bytes_fetched, st1.clusters_touched) == \
            (st2.n_fetched, st2.bytes_fetched, st2.clusters_touched)

    # degraded retrieval: kill the same n-k nodes everywhere so the
    # kernel GF decode path (non-systematic indices) actually runs
    for s in (s_np, s_kn):
        for c in s.clusters:
            c.kill_nodes([0, 2, 4, 6, 8])
    for (fn, b) in files:
        assert s_np.get_file("u", fn)[0] == b
    for (fn, b), (out, _) in zip(files, s_kn.get_files("u", names)):
        assert out == b


def test_engines_differential_multi_user():
    """ULB binding across users with rollover pressure, both engines."""
    blob_a = _data(50_000, seed=60)
    blob_b = _data(50_000, seed=61)
    stores = {}
    for eng in ("numpy", "kernel"):
        s = _store(eng, seed=3)
        s.put_files("alice", [("a1", blob_a), ("a2", blob_b)])
        s.put_files("bob", [("b1", blob_a)])  # other cluster: no dedup
        stores[eng] = s
    assert stores["numpy"].stats() == stores["kernel"].stats()
    for user, fn, blob in (("alice", "a1", blob_a), ("bob", "b1", blob_a)):
        o_np, _ = stores["numpy"].get_file(user, fn)
        o_kn, _ = stores["kernel"].get_file(user, fn)
        assert o_np == o_kn == blob


def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch):
    """``use_compile_cache`` honours the env var, else one in-checkout path."""
    import pathlib

    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert ops.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # set nothing

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = ops.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert ops.use_compile_cache() == first  # same path every call
        checkout = pathlib.Path(ops.__file__).resolve().parents[3]
        assert pathlib.Path(first) == checkout / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
