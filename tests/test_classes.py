"""Storage-class API: policies, pools, futures, deletes, per-class stats.

Contract families for ``repro.core.classes`` + the class-aware store:

* **policy plumbing** -- presets, pool partitioning, validation, and the
  legacy single-config deprecation shim (byte-identical to an explicit
  one-class store; hypothesis differential where installed).
* **pool isolation** -- classes never dedup across pools unless their
  dedup scope is ``"global"``; every cluster carries its own ``(n, k)``.
* **mixed-window equivalence** -- a flush window carrying both classes
  is byte-identical to sequential per-user, per-class
  ``put_files``/``get_files``, on both engines, while issuing
  O(code buckets x length buckets) GF/SHA-1 launches (the CI
  launch-count lane).
* **futures + delete ordering** -- scheduler submits return
  ``RequestFuture`` handles; queued deletes serialize with puts/gets in
  submission order.
* **repair** -- a failure storm over a mixed store rebuilds both pools
  with each cluster's own code and a balanced ``RepairReport``.
"""

import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.classes import StorageClass, partition_pools
from repro.core.store import SEARSStore
from repro.core.workload import MixedClassConfig, mixed_class_trace

ENGINES = ["numpy", "kernel", "fused"]


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.int64).astype(np.uint8).tobytes()


def _mixed_store(engine="numpy", num_clusters=8, **kw):
    kw.setdefault("node_capacity", 64 << 20)
    return SEARSStore(classes=[StorageClass.realtime(),
                               StorageClass.archival()],
                      num_clusters=num_clusters, engine=engine, **kw)


def _node_pieces(store):
    return [n._pieces for c in store.clusters for n in c.nodes]


# ---------------------------------------------------------- StorageClass ---
def test_presets_and_policy_axes():
    rt, ar = StorageClass.realtime(), StorageClass.archival()
    assert (rt.n, rt.k) == (10, 5) and rt.binding == "ulb"
    assert (ar.n, ar.k) == (14, 10) and ar.binding == "clb"
    assert ar.storage_overhead < rt.storage_overhead  # archival is leaner
    assert ar.chunker.avg_size > rt.chunker.avg_size
    assert rt.pool_tag == "realtime" and ar.pool_tag == "archival"
    custom = StorageClass.realtime(name="hot", k=2, n=6)
    assert (custom.n, custom.k, custom.name) == (6, 2, "hot")


def test_storage_class_validation():
    with pytest.raises(ValueError):
        StorageClass(name="bad", n=4, k=8)  # k > n
    with pytest.raises(ValueError):
        StorageClass(name="bad", chunk_min=0)
    with pytest.raises(ValueError):
        StorageClass(name="bad", dedup="sometimes")
    with pytest.raises(ValueError):
        StorageClass(name="")
    with pytest.raises(ValueError, match="incompatible"):
        # ULB's dedup scope is the bound cluster -- a global scope can
        # never take effect, so the combination is rejected up front
        StorageClass(name="bad", binding="ulb", dedup="global")


def test_partition_pools_shapes():
    rt, ar = StorageClass.realtime(), StorageClass.archival(weight=3.0)
    pools = partition_pools([rt, ar], 8)
    assert sorted(i for p in pools.values() for i in p) == list(range(8))
    assert len(pools["archival"]) > len(pools["realtime"])  # weighted
    # classes sharing a pool tag must agree on (n, k)
    with pytest.raises(ValueError, match="disagree"):
        partition_pools([StorageClass(name="a", pool="p", n=10, k=5),
                         StorageClass(name="b", pool="p", n=14, k=10)], 8)
    with pytest.raises(ValueError, match="clusters"):
        partition_pools([rt, ar], 1)  # fewer clusters than pools
    with pytest.raises(ValueError, match="duplicate"):
        partition_pools([rt, StorageClass.realtime()], 8)


def test_shared_pool_tag_shares_clusters():
    a = StorageClass(name="a", pool="shared", n=8, k=4)
    b = StorageClass(name="b", pool="shared", n=8, k=4, chunk_avg=8192,
                     chunk_max=16384, binding="clb")
    s = SEARSStore(classes=[a, b], num_clusters=4)
    assert s.pools == {"shared": (0, 1, 2, 3)}
    assert all((c.n, c.k) == (8, 4) for c in s.clusters)


# ------------------------------------------------------- deprecation shim --
def test_legacy_kwargs_warn_once_and_match_explicit_class():
    with pytest.warns(DeprecationWarning, match="single-config"):
        legacy = SEARSStore(n=8, k=4, binding="clb", num_clusters=4,
                            node_capacity=64 << 20, seed=3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # explicit classes= must not warn
        explicit = SEARSStore(
            classes=[StorageClass(name="default", n=8, k=4, binding="clb")],
            num_clusters=4, node_capacity=64 << 20, seed=3)

    for store in (legacy, explicit):
        store.put_files("u", [("a", _data(40_000, seed=1)),
                              ("b", _data(25_000, seed=2))])
        store.put_file("v", "c", _data(40_000, seed=1))  # cross-user dedup
        store.delete_file("u", "b")
    assert legacy.stats() == explicit.stats()
    assert _node_pieces(legacy) == _node_pieces(explicit)
    assert legacy.get_file("u", "a")[0] == explicit.get_file("u", "a")[0]


def test_default_construction_does_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        SEARSStore(num_clusters=4)


def test_classes_plus_legacy_kwargs_rejected():
    with pytest.raises(ValueError, match="not both"):
        SEARSStore(classes=[StorageClass.realtime()], n=10, k=5)


@settings(max_examples=8, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=12_000),
                min_size=1, max_size=4))
def test_shim_differential_property(blobs):
    """Legacy-kwarg store == explicit one-class store over small traces."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = SEARSStore(n=6, k=3, binding="ulb", num_clusters=3,
                            node_capacity=64 << 20)
    explicit = SEARSStore(
        classes=[StorageClass(name="default", n=6, k=3, binding="ulb")],
        num_clusters=3, node_capacity=64 << 20)
    for i, blob in enumerate(blobs):
        legacy.put_file(f"u{i % 2}", f"f{i}", blob)
        explicit.put_file(f"u{i % 2}", f"f{i}", blob)
    assert legacy.stats() == explicit.stats()
    assert _node_pieces(legacy) == _node_pieces(explicit)
    for i, blob in enumerate(blobs):
        assert legacy.get_file(f"u{i % 2}", f"f{i}")[0] == blob
        assert explicit.get_file(f"u{i % 2}", f"f{i}")[0] == blob


# --------------------------------------------------------- pool isolation --
def test_pools_never_dedup_across_classes_by_default():
    s = _mixed_store()
    blob = _data(60_000, seed=5)
    rt_up = s.put_file("alice", "hot", blob, storage_class="realtime")
    ar_up = s.put_file("alice", "cold", blob, storage_class="archival")
    assert rt_up.n_new_chunks > 0
    assert ar_up.n_new_chunks > 0  # same bytes stored again: no cross-pool
    rt_pool = set(s.pools["realtime"])
    ar_pool = set(s.pools["archival"])
    rt_meta = s.switching["alice"].get_meta("hot")
    ar_meta = s.switching["alice"].get_meta("cold")
    assert {cl for _, cl in rt_meta.entries} <= rt_pool
    assert {cl for _, cl in ar_meta.entries} <= ar_pool
    assert rt_meta.storage_class == "realtime"
    assert ar_meta.storage_class == "archival"


def test_global_dedup_scope_crosses_pools():
    # a global-scope class may reference chunks landed by another class --
    # same (n, k) is NOT required because the code resolves per cluster
    hot = StorageClass(name="hot", n=10, k=5, binding="clb", dedup="pool")
    cold = StorageClass(name="cold", n=14, k=10, binding="clb",
                        dedup="global", chunk_min=1024, chunk_avg=4096,
                        chunk_max=8192)  # same chunker -> same chunk ids
    s = SEARSStore(classes=[hot, cold], num_clusters=4,
                   node_capacity=64 << 20)
    blob = _data(60_000, seed=6)
    s.put_file("u", "a", blob, storage_class="hot")
    up = s.put_file("u", "b", blob, storage_class="cold")
    assert up.n_new_chunks == 0  # deduped against the hot pool's chunks
    meta = s.switching["u"].get_meta("b")
    assert {cl for _, cl in meta.entries} <= set(s.pools["hot"])
    # retrieval of the cross-pool file decodes with the owning cluster's
    # (10, 5) code even though the file's class is (14, 10)
    assert s.get_file("u", "b")[0] == blob


def test_unknown_storage_class_fails_cleanly():
    s = _mixed_store()
    with pytest.raises(KeyError, match="unknown storage class"):
        s.put_file("u", "f", _data(1000), storage_class="glacial")
    assert s.n_files == 0
    s.put_file("u", "f", _data(9_000, seed=1), storage_class="realtime")
    with pytest.raises(KeyError, match="stored under class"):
        s.get_file("u", "f", storage_class="archival")


def test_unknown_class_fails_only_its_request():
    s = _mixed_store()
    sched = s.scheduler()
    ok = sched.submit_put("a", [("f", _data(9_000, seed=1))],
                          storage_class="realtime")
    bad = sched.submit_put("b", [("g", _data(9_000, seed=2))],
                           storage_class="nope")
    sched.flush()
    assert ok.ok and bad.status == "failed"
    assert isinstance(bad.error, KeyError)


# --------------------------------------------------- mixed-window windows --
@pytest.mark.parametrize("engine", ENGINES)
def test_mixed_class_flush_equals_sequential_per_class(engine):
    """One mixed realtime+archival flush == sequential per-class calls."""
    trace = mixed_class_trace(MixedClassConfig(n_users=3))

    seq = _mixed_store(engine=engine)
    seq_up = [(u, cls, seq.put_files(u, files, storage_class=cls))
              for u, files, cls in trace]

    coal = _mixed_store(engine=engine)
    sched = coal.scheduler()
    futures = [(u, files, cls,
                sched.submit_put(u, files, storage_class=cls))
               for u, files, cls in trace]
    sched.flush()

    for (u, files, cls, fut), (_, _, up) in zip(futures, seq_up):
        assert fut.done(), fut.exception()
        assert fut.result() == up
    assert seq.stats() == coal.stats()
    assert seq.stats().per_class == coal.stats().per_class
    assert _node_pieces(seq) == _node_pieces(coal)

    # retrieval: one mixed get window == sequential per-class gets
    seq_out = [seq.get_files(u, [fn for fn, _ in files])
               for u, files, _ in trace]
    get_futs = [sched.submit_get(u, [fn for fn, _ in files])
                for u, files, _ in trace]
    sched.flush()
    for (u, files, _), fut, outs in zip(trace, get_futs, seq_out):
        for (fn, blob), (got_c, st_c), (got_s, st_s) in zip(
                files, fut.result(), outs):
            assert got_c == got_s == blob
            assert (st_c.n_fetched, st_c.bytes_fetched) == \
                (st_s.n_fetched, st_s.bytes_fetched)


def test_mixed_window_launch_counts_are_o_buckets():
    """A 2-class window costs O(code buckets x length buckets) launches --
    doubling the files per class must not change the launch count."""
    from repro.kernels.launches import LAUNCHES

    def run(files_per_class):
        s = _mixed_store(engine="kernel")
        sched = s.scheduler()
        for i in range(files_per_class):
            sched.submit_put(f"u{i}", [(f"rt{i}", _data(30_000, seed=i))],
                             storage_class="realtime")
            sched.submit_put(f"v{i}",
                             [(f"ar{i}", _data(30_000, seed=100 + i))],
                             storage_class="archival")
        before = LAUNCHES.snapshot()
        reqs = sched.flush()
        assert all(r.ok for r in reqs), [r.error for r in reqs]
        return LAUNCHES.delta(before)

    small, big = run(3), run(6)
    # one gear pass per chunker config, one fixed-shape SHA-1 batch
    assert small.gear == big.gear == 2
    assert small.sha1 == big.sha1 == 1
    # GF launches bucket by (code, padded length): same buckets -> same
    # count no matter how many files the window carries
    assert small.gf == big.gf
    assert big.gf >= 2  # at least one launch per class's code


def test_same_chunker_classes_share_one_gear_pass():
    from repro.kernels.launches import LAUNCHES
    a = StorageClass(name="a", n=10, k=5)
    b = StorageClass(name="b", n=14, k=10)  # same default chunker as a
    s = SEARSStore(classes=[a, b], num_clusters=4, node_capacity=64 << 20,
                   engine="kernel")
    sched = s.scheduler()
    sched.submit_put("u", [("f", _data(20_000, seed=1))], storage_class="a")
    sched.submit_put("v", [("g", _data(20_000, seed=2))], storage_class="b")
    before = LAUNCHES.snapshot()
    sched.flush()
    assert LAUNCHES.delta(before).gear == 1


# ------------------------------------------------- futures + delete order --
def test_futures_resolve_at_flush_and_reraise():
    s = _mixed_store()
    sched = s.scheduler()
    fut = sched.submit_put("u", [("f", _data(9_000, seed=1))],
                           storage_class="realtime")
    assert not fut.done() and fut.status == "queued"
    sched.flush()
    assert fut.done() and fut.ok
    assert fut.result()[0].filename == "f"
    bad = sched.submit_get("u", ["missing"])
    sched.flush()
    assert bad.done() and bad.exception() is not None
    with pytest.raises(KeyError):
        bad.result()


def test_future_result_flushes_in_submission_order():
    """result() on a queued future drains the queue -- earlier submits
    (including other users') execute first, exactly like flush()."""
    s = _mixed_store()
    blob = _data(9_000, seed=2)
    sched = s.scheduler()
    put = sched.submit_put("u", [("f", blob)], storage_class="archival")
    get = sched.submit_get("u", ["f"])
    out = get.result()  # resolves the whole queue: put ran first
    assert out[0][0] == blob
    assert put.done() and put.ok
    assert sched.pending == 0


def test_submitted_delete_serializes_with_queued_gets():
    """put -> get -> delete -> get in one flush behaves sequentially."""
    s = _mixed_store()
    blob = _data(12_000, seed=3)
    sched = s.scheduler()
    p = sched.submit_put("u", [("f", blob)], storage_class="realtime")
    g1 = sched.submit_get("u", ["f"])
    d = sched.submit_delete("u", ["f"])
    g2 = sched.submit_get("u", ["f"])
    sched.flush()
    assert p.ok and g1.ok and d.ok
    assert g1.result()[0][0] == blob  # submitted before the delete
    assert d.result() == ["f"]
    assert g2.status == "failed"  # submitted after the delete
    assert isinstance(g2.error, KeyError)
    assert sched.stats.n_delete_windows == 1
    assert s.n_files == 0 and s.stats().n_unique_chunks == 0


def test_direct_delete_is_one_request_flush():
    s = _mixed_store()
    s.put_file("u", "f", _data(10_000, seed=4), storage_class="archival")
    s.delete_file("u", "f")
    assert s.n_files == 0
    with pytest.raises(KeyError):
        s.delete_file("u", "f")  # missing file still raises


def test_delete_failure_isolated_in_window():
    s = _mixed_store()
    s.put_file("u", "f", _data(10_000, seed=5), storage_class="realtime")
    sched = s.scheduler()
    bad = sched.submit_delete("v", ["nope"])
    ok = sched.submit_delete("u", ["f"])
    sched.flush()
    assert bad.status == "failed" and isinstance(bad.error, KeyError)
    assert ok.ok and s.n_files == 0


# --------------------------------------------------------- per-class stats -
def test_per_class_stats_breakdown():
    s = _mixed_store()
    hot = _data(40_000, seed=6)
    cold = _data(80_000, seed=7)
    s.put_file("u", "hot", hot, storage_class="realtime")
    s.put_file("u", "cold", cold, storage_class="archival")
    s.put_file("v", "cold2", cold, storage_class="archival")  # CLB dedups
    stats = s.stats()
    rt, ar = stats.per_class["realtime"], stats.per_class["archival"]
    assert (rt.n, rt.k, rt.redundancy_overhead) == (10, 5, 2.0)
    assert (ar.n, ar.k, ar.redundancy_overhead) == (14, 10, 1.4)
    assert rt.logical_bytes == len(hot)
    assert ar.logical_bytes == 2 * len(cold)
    assert (rt.n_files, ar.n_files) == (1, 2)
    # pool slices tile the store: totals reconcile
    assert rt.piece_bytes + ar.piece_bytes == stats.piece_bytes
    assert rt.logical_bytes + ar.logical_bytes == stats.logical_bytes
    assert (rt.n_unique_chunks + ar.n_unique_chunks
            == stats.n_unique_chunks)
    assert rt.index_bytes + ar.index_bytes == stats.index_bytes
    # the paper's efficiency comparison, now per configuration: the
    # deduped archival pool beats realtime despite double the logical data
    assert ar.dedup_ratio > rt.dedup_ratio
    # physical overhead tracks each class's n/k (plus piece padding)
    assert rt.piece_bytes / rt.logical_bytes == pytest.approx(2.0, rel=0.02)
    assert ar.piece_bytes / (len(cold)) == pytest.approx(1.4, rel=0.02)


def test_single_class_store_stats_has_one_slice():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        s = SEARSStore(n=10, k=5, num_clusters=4, node_capacity=64 << 20)
    s.put_file("u", "f", _data(20_000, seed=8))
    stats = s.stats()
    assert set(stats.per_class) == {"default"}
    d = stats.per_class["default"]
    assert d.piece_bytes == stats.piece_bytes
    assert d.logical_bytes == stats.logical_bytes
    assert d.index_bytes == stats.index_bytes


def test_realtime_wins_retrieval_and_archival_wins_overhead():
    """The paper's per-class trade-off on a mixed trace: the real-time
    class (ULB, (10,5)) reads back faster on modelled retrieval time, the
    archival class (CLB, (14,10), global dedup) stores fewer physical
    bytes per logical byte."""
    from repro.core.latency import calibrate
    s = _mixed_store(node_capacity=1 << 30, latency=calibrate())
    trace = mixed_class_trace(MixedClassConfig(
        n_users=3, hot_files_per_user=3, cold_files_per_user=2))
    for user, files, cls in trace:
        s.put_files(user, files, storage_class=cls)
    times: dict[str, list[float]] = {}
    for user, files, cls in trace:
        for _, st in s.get_files(user, [fn for fn, _ in files]):
            times.setdefault(cls, []).append(st.time_s)
    assert np.mean(times["realtime"]) < np.mean(times["archival"])
    pc = s.stats().per_class
    rt, ar = pc["realtime"], pc["archival"]
    assert (ar.piece_bytes / ar.logical_bytes
            < rt.piece_bytes / rt.logical_bytes)


# ----------------------------------------------------------------- repair --
@pytest.mark.parametrize("engine", ENGINES)
def test_storm_repair_rebuilds_both_classes(engine):
    """repair_all heals both pools, each with its cluster's own (n, k)."""
    s = _mixed_store(engine=engine)
    trace = mixed_class_trace(MixedClassConfig(n_users=2))
    for u, files, cls in trace:
        s.put_files(u, files, storage_class=cls)
    baseline = {(u, fn): blob for u, files, _ in trace
                for fn, blob in files}

    # storm: wipe nodes in every populated cluster of both pools, staying
    # within each cluster's own n - k loss tolerance
    hit = {"realtime": 0, "archival": 0}
    for c in s.clusters:
        if c.used == 0:
            continue
        pool = next(t for t, ids in s.pools.items()
                    if c.cluster_id in ids)
        wipe = min(c.n - c.k, 3)
        c.replace_nodes(list(range(wipe)))
        hit[pool] += wipe
    assert hit["realtime"] > 0 and hit["archival"] > 0

    report = s.repair_all()
    assert report.balanced
    assert not report.unrecoverable and not report.failed
    rebuilt_pools = {next(t for t, ids in s.pools.items() if cl in ids)
                     for _, cl in report.rebuilt}
    assert rebuilt_pools == {"realtime", "archival"}
    # pieces per chunk match each cluster's own n again: full n-k kills
    # survive in both pools
    for c in s.clusters:
        if c.used:
            c.kill_nodes(list(range(c.n - c.k)))
    for (u, fn), blob in baseline.items():
        assert s.get_file(u, fn)[0] == blob


def test_read_repair_hint_uses_cluster_k():
    s = _mixed_store()
    s.put_file("u", "cold", _data(50_000, seed=9), storage_class="archival")
    cluster = next(c for c in s.clusters
                   if c.cluster_id in s.pools["archival"] and c.used)
    cluster.replace_nodes([0])  # systematic piece lost -> degraded read
    out, _ = s.get_file("u", "cold")
    assert s.repair.pending > 0  # hint queued against the (14, 10) cluster
    report = s.repair.drain()
    assert report.pieces_rebuilt > 0 and report.balanced
