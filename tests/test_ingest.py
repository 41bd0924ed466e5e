"""Device-batched CDC ingest: chunk_blobs differential + launch contracts.

Three contracts introduced by the batched chunking stage:

* ``engine.chunk_blobs`` is byte-identical to the per-file
  ``Chunker.chunk_spans`` host oracle on both engines, across every edge
  case (empty file, sub-min_size file, forced max_size cuts, candidates
  at file seams, shared content across a window);
* one put window issues O(1) gear + O(1) SHA-1 + O(length buckets) GF
  launches regardless of how many files/users it carries (the CI
  launch-count regression lane);
* repeated windows of varying sizes reuse a bounded set of compiled gear
  launches (``bucket_len`` quantization -- the jit-cache blowup fix),
  proven by the trace-time counters in ``kernels.launches``.
"""

import numpy as np
import pytest

from repro.core.chunking import WINDOW, Chunker, chunk_spans_batch
from repro.core.engine import FusedEngine, KernelEngine, NumpyEngine
from repro.core.store import SEARSStore


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.int64).astype(np.uint8).tobytes()


ENGINES = [NumpyEngine, KernelEngine, FusedEngine]


def _edge_case_window():
    shared = _data(30_000, seed=9)
    return [
        b"",                              # empty file
        b"x",                             # single byte
        _data(500, seed=1),               # < min_size: one tail chunk
        _data(1024, seed=2),              # == min_size
        b"\x00" * 40_000,                 # no candidates: forced max cuts
        _data(50_000, seed=3),            # multi-chunk file
        _data(50_000, seed=3),            # exact duplicate in same window
        shared + _data(4_000, seed=4),    # shared prefix
        _data(4_000, seed=5) + shared,    # shared suffix (seam-shifted)
        _data(8192 * 3, seed=6),          # tile-aligned length
    ]


# ------------------------------------------------------- differential ------
@pytest.mark.parametrize("engine_cls", ENGINES)
def test_chunk_blobs_matches_host_oracle(engine_cls):
    chunker = Chunker()
    blobs = _edge_case_window()
    want = [chunker.chunk_spans(b) for b in blobs]
    got = engine_cls().chunk_blobs(chunker, blobs)
    assert got == want


@pytest.mark.parametrize("engine_cls", ENGINES)
def test_chunk_blobs_duplicate_files_chunk_identically(engine_cls):
    """Dedup depends on identical content producing identical spans even
    when the two copies sit at different stream offsets of one window."""
    chunker = Chunker()
    blob = _data(40_000, seed=11)
    got = engine_cls().chunk_blobs(
        chunker, [_data(7_777, seed=12), blob, _data(123, seed=13), blob])
    assert got[1] == got[3] == chunker.chunk_spans(blob)


@pytest.mark.parametrize("engine_cls", ENGINES)
def test_chunk_blobs_small_min_size_head_candidates(engine_cls):
    """min_size < WINDOW exercises the per-file history reset: candidates
    in the first 31 bytes of a file are selectable and must match the
    oracle's zero-history hash, not the contaminated stream hash."""
    chunker = Chunker(min_size=8, avg_size=64, max_size=256)
    assert chunker.min_size < WINDOW
    blobs = [_data(n, seed=20 + n) for n in (40, 100, 1000, 5000)]
    want = [chunker.chunk_spans(b) for b in blobs]
    assert engine_cls().chunk_blobs(chunker, blobs) == want


def test_chunk_spans_batch_seam_boundary():
    """A candidate firing exactly at a file's last byte cuts at the seam;
    the next file's spans must be unaffected by its neighbour."""
    chunker = Chunker()
    a, b = _data(20_000, seed=30), _data(20_000, seed=31)
    got = chunk_spans_batch(chunker, [a, b])
    assert got[0] == chunker.chunk_spans(a)
    assert got[1] == chunker.chunk_spans(b)
    # spans cover each file exactly
    assert sum(l for _, l in got[0]) == len(a)
    assert got[0][-1][0] + got[0][-1][1] == len(a)


def test_chunk_blobs_forced_max_cuts_match():
    """Zero-fill content has no gear candidates: every cut is a forced
    max_size cut and the batched path must reproduce them exactly."""
    chunker = Chunker()
    spans = NumpyEngine().chunk_blobs(chunker, [b"\x00" * 40_000])[0]
    sizes = [l for _, l in spans]
    assert sizes[:-1] == [chunker.max_size] * (len(sizes) - 1)
    assert spans == chunker.chunk_spans(b"\x00" * 40_000)


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
def test_store_roundtrip_with_batched_chunking(engine):
    """End-to-end: multi-file window uploads and reads back byte-exact."""
    s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                   binding="ulb", engine=engine)
    files = [(f"f{i}", b) for i, b in enumerate(_edge_case_window())]
    s.put_files("u", files)
    for (fn, blob), (out, _) in zip(files, s.get_files(
            "u", [fn for fn, _ in files])):
        assert out == blob


# ----------------------------------------------- launch-count regression ---
def test_put_window_launch_counts():
    """One put window of N files: 1 gear + 1 SHA-1 + O(buckets) GF.

    The CI regression lane: any change that re-serializes dispatch (per
    file or per chunk) blows these counts up by orders of magnitude.
    """
    from repro.kernels.launches import LAUNCHES

    s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                   binding="ulb", engine="kernel")
    files = [(f"f{i}", _data(30_000 + 1000 * i, seed=40 + i))
             for i in range(12)]
    before = LAUNCHES.snapshot()
    s.put_files("u", files)
    delta = LAUNCHES.delta(before)
    assert delta.gear == 1, f"chunking re-serialized: {delta.gear} launches"
    assert delta.sha1 == 1, f"hashing re-serialized: {delta.sha1} launches"
    # encode buckets: chunk lens in (min_size, max_size] pad to piece-len
    # buckets of TILE_L -- a handful, never O(chunks)
    assert 1 <= delta.gf <= 8, f"encode re-serialized: {delta.gf} launches"


def test_multi_user_flush_single_gear_launch():
    """A cross-user flush window chunks all users in one device pass."""
    from repro.kernels.launches import LAUNCHES

    s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                   binding="ulb", engine="kernel")
    sched = s.scheduler()
    for u in range(4):
        sched.submit_put(f"user{u}", [(f"u{u}/f{i}", _data(20_000, seed=u * 8 + i))
                                      for i in range(3)])
    before = LAUNCHES.snapshot()
    reqs = sched.flush()
    assert all(r.ok for r in reqs)
    delta = LAUNCHES.delta(before)
    assert delta.gear == 1 and delta.sha1 == 1
    assert sched.stats.gear_launches == 1


def test_numpy_engine_chunking_stays_off_device():
    """NumpyEngine chunking is pure host numpy: no gear launches."""
    from repro.kernels.launches import LAUNCHES

    s = SEARSStore(n=10, k=5, num_clusters=2, node_capacity=64 << 20,
                   binding="ulb", engine="numpy")
    before = LAUNCHES.snapshot()
    s.put_files("u", [("f", _data(50_000, seed=50))])
    assert LAUNCHES.delta(before).gear == 0


def test_fused_window_launch_counts():
    """One fused put window: 1 gear + O(piece-len buckets) fused launches,
    zero staged SHA-1/GF dispatches -- and strictly no more launches than
    the staged kernel engine on the identical window."""
    from repro.kernels.launches import LAUNCHES

    files = [(f"f{i}", _data(30_000 + 1000 * i, seed=40 + i))
             for i in range(12)]

    def window_delta(engine):
        s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                       binding="ulb", engine=engine)
        before = LAUNCHES.snapshot()
        s.put_files("u", files)
        return LAUNCHES.delta(before)

    staged = window_delta("kernel")
    fused = window_delta("fused")
    assert fused.gear == 1, f"chunking re-serialized: {fused.gear} launches"
    assert fused.sha1 == 0, "fused window still issued a staged SHA-1 batch"
    assert fused.gf == 0, "fused window still issued staged GF encodes"
    assert 1 <= fused.fused <= 8, \
        f"fused ingest re-serialized: {fused.fused} launches"
    assert fused.total <= staged.total, \
        f"fused window ({fused.total}) issued more launches than staged " \
        f"({staged.total})"


def test_fused_bucket_past_the_lane_cap_runs_equal_launches(monkeypatch):
    """A piece-length bucket with more chunks than ``FUSED_LANES`` runs as
    launches of exactly ``FUSED_LANES`` lanes at the bucket's SHA-1 cap:
    one shape however many chunks the window holds, and the same ids and
    pieces as the host."""
    from repro.core.hashing import chunk_id
    from repro.core.rs_code import RSCode
    from repro.kernels import ops
    from repro.kernels.launches import LAUNCHES

    code = RSCode(10, 5)
    # 11 chunks of the 512-byte bucket (1..2560 bytes under (10, 5))
    lengths = np.random.default_rng(90).integers(1024, 2561, 11)
    blobs = [_data(int(n), seed=100 + i) for i, n in enumerate(lengths)]
    shapes = []
    fused = ops._fused_ingest_ref

    def record(*args):
        shapes.append(tuple(np.shape(a) for a in args[1:]))
        return fused(*args)
    monkeypatch.setattr(ops, "_fused_ingest_ref", record)
    monkeypatch.setattr(ops, "FUSED_LANES", 4)
    before = LAUNCHES.snapshot()
    ids, pieces = ops.fused_hash_encode_blobs(code, blobs, impl="ref")
    assert LAUNCHES.delta(before).fused == 3
    cap = (code.k * 512 + 9 + 63) // 64
    assert set(shapes) == {((4, cap, 16), (4,), (4, code.k, 512))}
    assert ids == [chunk_id(b) for b in blobs]
    assert pieces == [code.encode_bytes(b) for b in blobs]


def test_fused_steady_state_no_retrace():
    """Repeated put windows of the same shape must not retrace the fused
    jit entries (the per-window recompile failure mode)."""
    from repro.kernels.launches import TRACES

    s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                   binding="ulb", engine="fused")

    def put(tag):
        s.put_files("u", [(f"{tag}/f{i}", _data(25_000, seed=70 + i))
                          for i in range(4)])

    put("warm")  # compiles this window shape
    t0 = TRACES.snapshot()
    put("w1")
    put("w2")
    delta = TRACES.delta(t0)
    assert delta.fused == 0, "fused ingest retraced on a repeated window"
    assert delta.gear == 0, "gear retraced on a repeated window"


def test_fused_store_matches_numpy_store():
    """FusedEngine end state (stats, retrieved bytes) is byte-identical
    to NumpyEngine over a dedup-heavy mixed window."""
    blobs = _edge_case_window()
    files = [(f"f{i}", b) for i, b in enumerate(blobs)]

    def build(engine):
        s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                       binding="ulb", seed=3, engine=engine)
        up = s.put_files("u", files)
        got = s.get_files("u", [fn for fn, _ in files])
        return s, up, got

    sn, upn, gotn = build("numpy")
    sf, upf, gotf = build("fused")
    assert upf == upn
    assert [g[0] for g in gotf] == [g[0] for g in gotn]
    assert [g[1] for g in gotf] == [g[1] for g in gotn]
    assert sf.stats() == sn.stats()


# ------------------------------------------- double-buffered put windows ----
def _stream_windows(n_windows=3, seed=80):
    from repro.core.workload import StreamingConfig, streaming_window_trace
    cfg = StreamingConfig(n_windows=n_windows, users_per_window=2,
                          files_per_user=2, file_kb=24, seed=seed)
    return list(streaming_window_trace(cfg))


def _stream_store(engine, seed):
    return SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                      binding="ulb", seed=seed, engine=engine)


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
def test_scheduler_put_windows_match_sequential(engine):
    """One flush of several put windows (a get between each pair keeps
    them apart), each next window's chunk pass begun ahead, commits the
    same bytes, stats and placement as per-window put_files calls."""
    windows = _stream_windows()

    pipe = _stream_store(engine, seed=7)
    sched = pipe.scheduler()
    put_futs, get_futs = [], []
    for w, batch in enumerate(windows):
        if w:
            user, files = windows[w - 1][0]
            get_futs.append(sched.submit_get(user,
                                             [fn for fn, _ in files]))
        put_futs.append([sched.submit_put(u, fs) for u, fs in batch])
    sched.flush()
    assert sched.stats.n_put_windows == len(windows)
    assert sched.stats.n_pipelined_windows == len(windows) - 1

    seq = _stream_store(engine, seed=7)
    want_puts, want_gets = [], []
    for w, batch in enumerate(windows):
        if w:
            user, files = windows[w - 1][0]
            want_gets.append(seq.get_files(user, [fn for fn, _ in files]))
        want_puts.append([st for u, fs in batch
                          for st in seq.put_files(u, fs)])

    assert [[st for f in futs for st in f.result()]
            for futs in put_futs] == want_puts
    assert [f.result() for f in get_futs] == want_gets
    assert pipe.stats() == seq.stats()
    for cp, cs in zip(pipe.clusters, seq.clusters):
        for np_, ns in zip(cp.nodes, cs.nodes):
            assert np_._pieces == ns._pieces


@pytest.mark.parametrize("engine", ["kernel", "fused"])
@pytest.mark.parametrize("degraded", [False, True])
def test_scheduler_get_window_matches_get_files(engine, degraded):
    """One scheduler flush of several users' gets returns the same bytes
    and the same latency-model stats as per-file get_files calls
    (healthy and degraded: systematic memcpy vs real GF decode
    launches)."""
    windows = _stream_windows(seed=81)
    store = _stream_store(engine, seed=9)
    for batch in windows:
        for user, files in batch:
            store.put_files(user, files)
    if degraded:
        for c in store.clusters:
            c.kill_nodes([0, 2, 4, 6, 8])
    users = sorted({u for batch in windows for u, _ in batch})
    names = {u: [fn for batch in windows for v, fs in batch if v == u
                 for fn, _ in fs] for u in users}

    store.rng = np.random.default_rng(123)
    want = [store.get_files(u, [fn])[0] for u in users for fn in names[u]]
    store.rng = np.random.default_rng(123)  # same latency rng draws
    sched = store.scheduler()
    futs = [sched.submit_get(u, names[u]) for u in users]
    sched.flush()
    assert sched.stats.n_get_windows == 1
    got = [r for f in futs for r in f.result()]
    assert [g[0] for g in got] == [w[0] for w in want]
    assert [g[1] for g in got] == [w[1] for w in want]


def test_scheduler_pipelined_flush_matches_unpipelined():
    """A flush whose second put window is begun ahead of the first's
    host phases (n_pipelined_windows counts it) commits the same
    artifacts as per-window put_files calls."""
    filesA = [(f"a{i}", _data(15_000, seed=90 + i)) for i in range(3)]
    filesB = [(f"b{i}", _data(14_000, seed=95 + i)) for i in range(3)]

    def fresh():
        return SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20,
                          binding="ulb", seed=11, engine="fused")

    s = fresh()
    sched = s.scheduler()
    fa = sched.submit_put("alice", filesA)
    fg = sched.submit_get("alice", [fn for fn, _ in filesA[:1]])
    fb = sched.submit_put("bob", filesB)
    sched.flush()
    sst = sched.stats

    seq = fresh()
    ra = seq.put_files("alice", filesA)
    ga = seq.get_files("alice", [fn for fn, _ in filesA[:1]])
    rb = seq.put_files("bob", filesB)
    assert (fa.result(), fg.result(), fb.result(), s.stats()) == \
        (ra, ga, rb, seq.stats())
    for cp, cs in zip(s.clusters, seq.clusters):
        for np_, ns in zip(cp.nodes, cs.nodes):
            assert np_._pieces == ns._pieces
    assert sst.n_pipelined_windows >= 1
    # the fused engine's ingest launches land in the scheduler's counters
    assert sst.fused_launches >= 1 and sst.sha1_launches == 0


# ------------------------------------------------- retrace regression ------
def test_gear_stream_launches_do_not_retrace_across_sizes():
    """Varying window sizes reuse bucketed compiled shapes.

    ``_gear_hash_padded``/``_gear_ref_padded`` compile once per padded
    length; ``bucket_len`` quantizes lengths to power-of-two multiples of
    TILE so the compile count is O(log max_size), not O(#distinct sizes).
    """
    from repro.kernels import ops
    from repro.kernels.gear_cdc import bucket_len
    from repro.kernels.launches import TRACES, delta_all, snapshot_all

    rng = np.random.default_rng(60)
    sizes = [1, 100, 8192, 8193, 10_000, 12_345, 16_384, 20_000, 30_000,
             33_000, 40_000, 65_000]
    buckets = {bucket_len(n) for n in sizes}
    # both families in one snapshot: launch deltas and trace deltas below
    # are guaranteed to cover the same interval
    s0 = snapshot_all()
    for n in sizes:
        data = rng.integers(0, 256, size=n, dtype=np.int64).astype(np.uint8)
        h = ops.gear_hash_stream(data, impl="ref")
        assert h.shape == (n,)
    d = delta_all(s0)
    assert d["launches"].gear == len(sizes)  # every call dispatches...
    assert d["traces"].gear <= len(buckets)  # ...few shapes compile
    # second sweep: zero new traces -- the cache is warm for every bucket
    t1 = TRACES.snapshot()
    for n in sizes:
        data = rng.integers(0, 256, size=n, dtype=np.int64).astype(np.uint8)
        ops.gear_hash_stream(data, impl="ref")
    assert TRACES.delta(t1).gear == 0, "gear jit cache retraced"


def test_bucket_len_quantization():
    from repro.kernels.gear_cdc import TILE, bucket_len

    assert bucket_len(1) == TILE
    assert bucket_len(TILE) == TILE
    assert bucket_len(TILE + 1) == 2 * TILE
    assert bucket_len(3 * TILE) == 4 * TILE
    for n in (1, 8192, 20_000, 100_000):
        b = bucket_len(n)
        assert b >= n and b % TILE == 0
        assert (b // TILE) & (b // TILE - 1) == 0  # power-of-two tiles


# ------------------------------------------------------------ auto-flush ---
def _store(**kw):
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    return SEARSStore(n=10, k=5, binding="ulb", seed=5, **kw)


def test_size_triggered_flush_is_byte_identical_to_manual():
    """flush_bytes auto-flush produces the same artifacts as manual
    flushes of the same submit sequence."""
    batches = [(f"user{u}", [(f"u{u}/f{i}", _data(15_000, seed=u * 4 + i))
                             for i in range(2)]) for u in range(4)]

    manual = _store(engine="kernel")
    m_sched = manual.scheduler()
    for user, files in batches:
        m_sched.submit_put(user, files)
    m_sched.flush()

    auto = _store(engine="kernel")
    a_sched = auto.scheduler()
    a_sched.flush_bytes = 50_000  # ~2 users' payload per window
    reqs = [a_sched.submit_put(user, files) for user, files in batches]
    a_sched.flush()  # drain the remainder window, if any
    assert all(r.ok for r in reqs)
    assert a_sched.stats.n_auto_flushes >= 1
    assert manual.stats() == auto.stats()
    for cm, ca in zip(manual.clusters, auto.clusters):
        for nm, na in zip(cm.nodes, ca.nodes):
            assert nm._pieces == na._pieces  # bytes on nodes identical


def test_size_triggered_flush_fires_at_threshold():
    s = _store(engine="numpy")
    sched = s.scheduler()
    sched.flush_bytes = 20_000
    r1 = sched.submit_put("a", [("f1", _data(8_000, seed=1))])
    assert r1.status == "queued" and sched.pending == 1
    assert sched.pending_bytes == 8_000
    r2 = sched.submit_put("b", [("f2", _data(12_000, seed=2))])
    # threshold reached -> whole window flushed on submit
    assert r1.ok and r2.ok and sched.pending == 0
    assert sched.pending_bytes == 0
    assert sched.stats.n_auto_flushes == 1
    assert s.get_file("a", "f1")[0] == _data(8_000, seed=1)


def test_auto_flush_counts_generator_payloads():
    """Byte accounting reads the queue's materialized copy, not the
    caller's iterable (which submit already exhausted)."""
    s = _store(engine="numpy")
    sched = s.scheduler()
    sched.flush_bytes = 10_000
    r = sched.submit_put("a", iter([("f", _data(12_000, seed=1))]))
    assert r.ok and sched.stats.n_auto_flushes == 1
    assert s.get_file("a", "f")[0] == _data(12_000, seed=1)


def test_interval_triggered_flush_uses_injected_clock():
    now = [0.0]
    s = _store(engine="numpy")
    sched = s.scheduler()
    sched.flush_interval, sched._clock = 5.0, lambda: now[0]
    r1 = sched.submit_put("a", [("f", _data(4_000, seed=3))])
    assert r1.status == "queued"  # window just opened
    now[0] = 4.0
    assert sched.poll() == []  # not yet expired
    now[0] = 5.5
    flushed = sched.poll()
    assert flushed == [r1.request] and r1.ok
    assert sched.stats.n_auto_flushes == 1


@pytest.mark.parametrize("payload", [5, np.zeros((3, 4), dtype=np.uint8),
                                     "not-bytes"])
def test_non_1d_payload_fails_only_its_request(payload):
    """Scalars / 2-D arrays / strings are rejected at validation and never
    join the shared chunk stream, so window neighbours still commit."""
    s = _store(engine="kernel")
    sched = s.scheduler()
    ok1 = sched.submit_put("alice", [("a", _data(12_000, seed=1))])
    bad = sched.submit_put("mallory", [("m", payload)])
    ok2 = sched.submit_put("bob", [("b", _data(12_000, seed=2))])
    sched.flush()
    assert ok1.ok and ok2.ok
    assert bad.status == "failed" and bad.error is not None
    assert s.get_file("alice", "a")[0] == _data(12_000, seed=1)
    assert s.get_file("bob", "b")[0] == _data(12_000, seed=2)


def test_malformed_file_pair_does_not_raise_at_submit():
    """A bad (name, data, extra) triple must fail at flush, per request --
    never out of submit_put after the request is already enqueued."""
    s = _store(engine="numpy")
    sched = s.scheduler()
    sched.flush_bytes = 1 << 30  # byte accounting runs, threshold never hit
    ok = sched.submit_put("alice", [("a", _data(8_000, seed=1))])
    bad = sched.submit_put("mallory", [("m", b"x", b"extra")])
    sched.flush()
    assert ok.ok
    assert bad.status == "failed" and bad.error is not None


def test_interval_triggered_flush_on_late_submit():
    from repro.core.scheduler import BatchScheduler

    now = [100.0]
    s = _store(engine="numpy")
    sched = BatchScheduler(s, flush_interval=2.0, clock=lambda: now[0])
    r1 = sched.submit_put("a", [("f1", _data(4_000, seed=4))])
    now[0] = 103.0  # next submit arrives after the window expired
    r2 = sched.submit_put("b", [("f2", _data(4_000, seed=5))])
    assert r1.ok and r2.ok and sched.pending == 0
