"""Block cache + write-back + SLO lanes: unit mechanics, store
integration (hit latency, write-back ack/drain, delete-race,
shard-drain coherence, cluster-loss re-home), scheduler priority
lanes/admission control, and the cache-on-vs-off differential proof."""

import numpy as np
import pytest

from differential import ShardTraceConfig, run_cache_differential
from repro.core.cache import BlockCache, CacheConfig
from repro.core.classes import StorageClass
from repro.core.sanitizer import Sanitizer
from repro.core.scheduler import AdmissionError, BatchScheduler
from repro.core.store import SEARSStore


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.int64).astype(np.uint8).tobytes()


def _store(binding="ulb", **kw):
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    return SEARSStore(n=10, k=5, binding=binding, **kw)


def _cid(i):
    return bytes([i]) + b"\x00" * 19


# ------------------------------------------------------ BlockCache units ----

def test_lru_evicts_oldest_clean_first_within_budget():
    c = BlockCache(CacheConfig(capacity_bytes=300))
    for i in range(3):
        c.fill(_cid(i), 0, b"x" * 100)
    c.lookup(_cid(0), 0)  # 0 becomes MRU
    c.fill(_cid(3), 0, b"y" * 100)  # over budget: evict LRU = 1
    assert (_cid(1), 0) not in c
    assert (_cid(0), 0) in c and (_cid(3), 0) in c
    assert c.stats.cached_bytes == 300
    assert c.stats.n_evictions == 1
    assert c.stats.n_hits == 1 and c.stats.n_misses == 0


def test_oversized_fill_and_duplicate_fill_are_noops():
    c = BlockCache(CacheConfig(capacity_bytes=100))
    c.fill(_cid(1), 0, b"z" * 101)  # never admissible
    assert len(c) == 0
    c.fill(_cid(2), 0, b"a" * 10)
    c.fill(_cid(2), 0, b"b" * 10)  # same copy key: first blob wins
    assert c.peek(_cid(2), 0) == b"a" * 10
    assert c.stats.n_insertions == 1 and c.stats.cached_bytes == 10


def test_dirty_entries_are_pinned_until_mark_clean():
    c = BlockCache(CacheConfig(capacity_bytes=250))
    task = c.put_dirty(_cid(1), 0, b"d" * 100, piece_len=20, reserved=200)
    for i in range(2, 5):
        c.fill(_cid(i), 0, b"c" * 100)  # pressure: clean entries churn
    assert c.is_dirty(_cid(1), 0) and (_cid(1), 0) in c
    assert c.stats.dirty_bytes == 100
    assert c.queued_tasks() == [task]
    c.mark_clean(task)
    assert not c.is_dirty(_cid(1), 0)
    assert c.stats.dirty_bytes == 0
    assert c.stats.n_writeback_chunks == 1
    c.fill(_cid(9), 0, b"e" * 100)
    c.fill(_cid(10), 0, b"e" * 100)  # now-clean old entry is evictable
    assert (_cid(1), 0) not in c


def test_discard_cancels_queued_upload_atomically():
    c = BlockCache(CacheConfig(capacity_bytes=1000))
    t1 = c.put_dirty(_cid(1), 0, b"a" * 50, piece_len=10, reserved=100)
    t2 = c.put_dirty(_cid(2), 1, b"b" * 50, piece_len=10, reserved=100)
    got = c.discard(_cid(1), 0)
    assert got is t1
    assert c.queued_tasks() == [t2]  # t1 left the queue with its entry
    assert c.stats.dirty_bytes == 50 and c.stats.cached_bytes == 50
    assert c.discard(_cid(1), 0) is None  # already gone
    c.fill(_cid(3), 0, b"c" * 10)
    assert c.discard(_cid(3), 0) is None  # clean: no task to return
    assert c.take_writeback() == [t2]


def test_take_writeback_respects_max_bytes_but_takes_at_least_one():
    c = BlockCache(CacheConfig(capacity_bytes=10_000))
    tasks = [c.put_dirty(_cid(i), 0, b"x" * 100, piece_len=20, reserved=200)
             for i in range(5)]
    first = c.take_writeback(max_bytes=1)  # at least one, oldest first
    assert first == tasks[:1]
    rest = c.take_writeback(max_bytes=250)  # 100+100 >= 250? stop at 300
    assert rest == tasks[1:4]
    c.requeue(rest)  # failed drain: head of queue, order kept
    assert c.take_writeback() == tasks[1:]
    assert c.stats.n_writeback_failures == 3


# ------------------------------------------------- store read-cache path ----

def test_cache_hit_serves_identical_bytes_and_is_faster():
    s = _store(binding="clb", cache=True)
    blob = _data(200_000, seed=3)
    s.put_file("u", "f", blob)
    cold, st_cold = s.get_file("u", "f")
    hot, st_hot = s.get_file("u", "f")
    assert cold == blob and hot == blob
    assert st_cold.n_cache_hits == 0
    assert st_hot.n_cache_hits == st_hot.n_chunks  # full hit
    assert st_hot.n_fetched == 0
    assert st_hot.time_s < st_cold.time_s
    cstats = s.stats().cache
    assert cstats is not None and cstats.n_hits == st_hot.n_chunks


def test_partial_hit_composes_with_miss_retrieval():
    # capacity below the file's chunk total: only a suffix stays cached
    s = _store(binding="clb",
               cache=CacheConfig(capacity_bytes=48 << 10))
    blob = _data(300_000, seed=4)
    s.put_file("u", "f", blob)
    s.get_file("u", "f")  # fill what fits
    hot, st = s.get_file("u", "f")
    assert hot == blob
    assert 0 < st.n_cache_hits < st.n_chunks  # genuinely partial
    assert st.n_fetched > 0


def test_cacheless_store_reports_no_hits_and_no_cache_stats():
    s = _store()
    blob = _data(100_000, seed=5)
    s.put_file("u", "f", blob)
    s.get_file("u", "f")
    _, st = s.get_file("u", "f")
    assert st.n_cache_hits == 0
    assert s.stats().cache is None


# ------------------------------------------------------------ write-back ----

@pytest.mark.parametrize("sanitize", [False, True])
def test_writeback_put_defers_upload_until_flush(sanitize):
    s = _store(cache=CacheConfig(write_back=True), sanitize=sanitize)
    blob = _data(150_000, seed=6)
    s.put_file("u", "f", blob)
    dirty = s.cache.dirty_count
    assert dirty > 0
    assert sum(c.used for c in s.clusters) == 0  # nothing landed yet
    assert sum(c._reserved for c in s.clusters) > 0  # but space is promised
    drained = s.flush()
    assert drained == dirty and s.cache.dirty_count == 0
    assert sum(c._reserved for c in s.clusters) == 0
    assert sum(c.used for c in s.clusters) > 0
    got, _ = s.get_file("u", "f")
    assert got == blob
    Sanitizer(s).check_ledger()


def test_dirty_chunk_is_readable_before_it_lands():
    s = _store(cache=CacheConfig(write_back=True))
    blob = _data(120_000, seed=7)
    s.put_file("u", "f", blob)
    got, st = s.get_file("u", "f")  # served from the pinned dirty bytes
    assert got == blob
    assert st.n_cache_hits == st.n_chunks and st.n_fetched == 0
    assert s.cache.dirty_count > 0  # the read did not force a drain


def test_over_dirty_limit_forces_partial_synchronous_drain():
    s = _store(cache=CacheConfig(capacity_bytes=1 << 20, write_back=True,
                                 max_dirty_bytes=64 << 10))
    for i in range(4):
        s.put_file("u", f"f{i}", _data(64_000, seed=20 + i))
    assert s.cache.stats.dirty_bytes <= s.cache.config.dirty_limit
    assert s.cache.stats.n_writeback_chunks > 0  # some landed early
    s.flush()
    for i in range(4):
        got, _ = s.get_file("u", f"f{i}")
        assert got == _data(64_000, seed=20 + i)


# ---------------------------------------------- delete vs queued upload ----

@pytest.mark.parametrize("sanitize", [False, True])
def test_delete_while_dirty_cancels_upload_and_reservation(sanitize):
    s = _store(cache=CacheConfig(write_back=True), sanitize=sanitize)
    blob = _data(100_000, seed=8)
    s.put_file("u", "f", blob)
    assert s.cache.dirty_count > 0
    s.delete_file("u", "f")
    assert s.cache.dirty_count == 0  # uploads canceled, never run
    assert sum(c._reserved for c in s.clusters) == 0
    assert s.flush() == 0
    assert sum(c.used for c in s.clusters) == 0
    assert s.stats().n_unique_chunks == 0


def test_submit_put_then_submit_delete_race_regression():
    """A put and its delete queued in the same flush: the delete must
    cancel the not-yet-drained upload, leaving no reservation, no
    pieces, no index record — the original write-back ordering bug."""
    s = _store(cache=CacheConfig(write_back=True), sanitize=True)
    sched = BatchScheduler(s)
    blob = _data(90_000, seed=9)
    put = sched.submit_put("u", [("f", blob)])
    delete = sched.submit_delete("u", ["f"])
    for req in sched.flush():
        assert req.error is None, req.error
    assert put.ok and delete.ok
    assert s.cache.dirty_count == 0
    assert sum(c._reserved for c in s.clusters) == 0
    assert sum(c.used for c in s.clusters) == 0
    assert s.stats().n_unique_chunks == 0
    Sanitizer(s).check_ledger()
    with pytest.raises(KeyError):
        s.get_file("u", "f")


# ----------------------------------------------------- topology barriers ----

def test_shard_drain_evicts_drained_buckets_and_flushes_dirty():
    s = _store(shards=4, cache=CacheConfig(write_back=True))
    blobs = {f"f{i}": _data(80_000, seed=30 + i) for i in range(6)}
    for fn, blob in blobs.items():
        s.put_file("u", fn, blob)
    s.flush()
    for fn in blobs:
        s.get_file("u", fn)  # read-fill the cache
    assert len(s.cache) > 0
    sid = s.shard_map.live_ids()[0]
    doomed = [key for key in s.cache.keys()
              if s.shard_map.shard_of_chunk(key[0]).shard_id == sid]
    survivors = [k for k in s.cache.keys() if k not in doomed]
    s.put_file("u", "late", _data(50_000, seed=40))  # dirty at drain time
    s.drain_shard(sid)
    assert s.cache.dirty_count == 0  # drain is a durability barrier
    for key in doomed:
        assert key not in s.cache  # coherence sweep
    for key in survivors:
        assert key in s.cache
    for fn, blob in blobs.items():
        got, _ = s.get_file("u", fn)
        assert got == blob
    got, _ = s.get_file("u", "late")
    assert got == _data(50_000, seed=40)


@pytest.mark.parametrize("sanitize", [False, True])
def test_cluster_loss_rehomes_dirty_chunks(sanitize):
    s = _store(num_clusters=3, cache=CacheConfig(write_back=True),
               sanitize=sanitize)
    blobs = {f"f{i}": _data(70_000, seed=50 + i) for i in range(4)}
    for fn, blob in blobs.items():
        s.put_file("u", fn, blob)
    tasks = s.cache.queued_tasks()
    assert tasks
    lost = tasks[0].cluster_id
    n_doomed = sum(1 for t in tasks if t.cluster_id == lost)
    assert n_doomed > 0
    s.declare_cluster_lost(lost)
    assert all(t.cluster_id != lost for t in s.cache.queued_tasks())
    assert s.cache.dirty_count > 0  # re-homed, not silently dropped
    assert s.clusters[lost]._reserved == 0
    s.flush()
    for fn, blob in blobs.items():
        got, _ = s.get_file("u", fn)
        assert got == blob
    Sanitizer(s).check_ledger()


# ---------------------------------------------- lanes + admission control ----

def _two_class_store(**kw):
    return SEARSStore(classes=[StorageClass.realtime(),
                               StorageClass.archival()],
                      num_clusters=4, node_capacity=64 << 20, **kw)


def test_lanes_run_realtime_before_archival():
    s = _two_class_store()
    s.put_files("a", [("f", _data(40_000, seed=60))],
                storage_class="archival")
    s.put_files("r", [("f", _data(40_000, seed=61))],
                storage_class="realtime")
    sched = BatchScheduler(s, lanes=True)
    arc = sched.submit_get("a", ["f"], storage_class="archival")
    rt = sched.submit_get("r", ["f"], storage_class="realtime")
    drained = sched.flush()
    assert [r.request_id for r in drained] == \
        [rt.request.request_id, arc.request.request_id]
    assert rt.ok and arc.ok


def test_admission_sheds_lower_priority_newest_first():
    s = _two_class_store()
    s.put_files("a", [("f", _data(30_000, seed=62))],
                storage_class="archival")
    s.put_files("r", [("f", _data(30_000, seed=63))],
                storage_class="realtime")
    sched = BatchScheduler(s, lanes=True, max_pending=2)
    arc1 = sched.submit_get("a", ["f"], storage_class="archival")
    arc2 = sched.submit_get("a", ["f"], storage_class="archival")
    arc3 = sched.submit_get("a", ["f"], storage_class="archival")
    # equal-priority overload: the *newcomer* is rejected (FIFO fairness)
    assert isinstance(arc3.request.error, AdmissionError)
    with pytest.raises(AdmissionError):
        arc3.result()
    # a realtime submit sheds the newest queued archival instead
    rt = sched.submit_get("r", ["f"], storage_class="realtime")
    assert isinstance(arc2.request.error, AdmissionError)
    sched.flush()
    assert rt.ok and arc1.ok
    assert sched.stats.n_admission_rejected == 1
    assert sched.stats.n_admission_shed == 1
    # exact accounting: every submitted future resolved one way
    outcomes = [arc1.ok, arc2.ok, arc3.ok, rt.ok]
    assert outcomes.count(True) == 2 and outcomes.count(False) == 2


def test_admission_never_sheds_equal_or_higher_priority():
    s = _two_class_store()
    s.put_files("r", [("f", _data(30_000, seed=64))],
                storage_class="realtime")
    sched = BatchScheduler(s, lanes=True, max_pending=1)
    rt1 = sched.submit_get("r", ["f"], storage_class="realtime")
    rt2 = sched.submit_get("r", ["f"], storage_class="realtime")
    assert rt1.request.error is None  # the queued one survives
    assert isinstance(rt2.request.error, AdmissionError)
    arc = sched.submit_get("r", ["f"], storage_class="archival")
    assert isinstance(arc.request.error, AdmissionError)  # can't shed rt1
    sched.flush()
    assert rt1.ok


# realtime p99 at peak archival load vs its unloaded p99
SLO_FACTOR = 1.5


def _pctl(xs, q):
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(round(q * (len(ys) - 1))))]


def _overload_arm(admission: bool) -> dict:
    """Closed-loop two-class sweep on a fake clock: a fixed realtime flow
    (3 users) rides a scheduler while archival demand steps from 1 to 48
    gets a window, four windows a rate.  Each window's admitted get
    bytes set the rho the next window is charged (1.5 MB is the box's
    absorbable demand), so shedding archival load is what keeps
    realtime latency flat."""
    from repro.core.latency import calibrate
    s = SEARSStore(classes=[StorageClass.realtime(),
                            StorageClass.archival()],
                   num_clusters=8, node_capacity=1 << 30,
                   latency=calibrate())
    now = [0.0]
    sched = BatchScheduler(s, clock=lambda: now[0], lanes=True,
                           max_pending=8 if admission else None)
    rt_files = [(f"rt/f{i}", _data(24 << 10, seed=7 + i)) for i in range(3)]
    arc_files = [(f"arc/f{i}", _data(48 << 10, seed=57 + i))
                 for i in range(4)]
    for u in range(3):
        s.put_files(f"rt{u}", rt_files, storage_class="realtime")
    for u in range(12):
        s.put_files(f"arc{u}", arc_files, storage_class="archival")
    box = {"prev": 0.0}

    def rho_fn(cluster_id):
        return min(0.95, box["prev"] / 1.5e6)

    out = {"p99": {}, "offered": {}, "done": {}, "rejected": {},
           "failed": {}}
    for key in ("offered", "done", "rejected", "failed"):
        out[key] = {"realtime": 0, "archival": 0}
    for rate in (1, 48):
        rt_times = []
        for w in range(4):
            # archival flood first: the lanes must reorder, and realtime
            # submits shed queued archival
            futs = [("archival", sched.submit_get(
                f"arc{(w * rate + j) % 12}", [arc_files[(w + j) % 4][0]],
                rho_fn=rho_fn, storage_class="archival"))
                for j in range(rate)]
            futs += [("realtime", sched.submit_get(
                f"rt{u}", [rt_files[w % 3][0]], rho_fn=rho_fn,
                storage_class="realtime")) for u in range(3)]
            sched.flush()
            admitted = 0
            for klass, fut in futs:
                out["offered"][klass] += 1
                if fut.error is None and fut.ok:
                    out["done"][klass] += 1
                    for _, st in fut.request.result:
                        admitted += st.file_bytes
                        if klass == "realtime":
                            rt_times.append(st.time_s)
                elif isinstance(fut.error, AdmissionError):
                    out["rejected"][klass] += 1
                else:
                    out["failed"][klass] += 1
            box["prev"] = admitted
            now[0] += 1.0
        out["p99"][rate] = _pctl(rt_times, 0.99)
        box["prev"] = 0.0  # cool the box between rates
    out["shed"] = sched.stats.n_admission_shed
    return out


def test_admission_control_keeps_realtime_p99_within_slo_at_peak():
    on, off = _overload_arm(True), _overload_arm(False)
    for arm in (on, off):
        for klass in ("realtime", "archival"):
            assert arm["offered"][klass] == (arm["done"][klass]
                                             + arm["rejected"][klass]
                                             + arm["failed"][klass])
    assert on["p99"][48] <= SLO_FACTOR * on["p99"][1]
    # the peak reached the knee: archival was shed or rejected, and
    # realtime never was while archival could give way
    assert on["rejected"]["archival"] or on["shed"]
    assert on["rejected"]["realtime"] == 0
    # the control is load-bearing: without it realtime drowns
    assert off["p99"][48] > SLO_FACTOR * off["p99"][1]


def test_cache_hit_p50_beats_cold_p50_by_5x():
    """A hot catalog read by several users from an archival (CLB) store:
    full cache hits stream from the switching node and skip the
    cross-cluster search, so their modelled p50 is >= 5x faster than
    the cache-less store's cold p50."""
    from repro.core.latency import calibrate
    catalog = [(f"c{j}", _data(24 << 10, seed=500 + j)) for j in range(6)]
    names = [fn for fn, _ in catalog]

    def replay(cache):
        s = SEARSStore(classes=[StorageClass.archival()], num_clusters=6,
                       node_capacity=1 << 30, latency=calibrate(),
                       cache=cache)
        for u in range(4):
            s.put_files(f"user{u}", catalog)
        cold, hit = [], []
        for _ in range(3):
            for u in range(4):
                for _, st in s.get_files(f"user{u}", names):
                    if st.n_cache_hits == st.n_chunks:
                        hit.append(st.time_s)
                    elif st.n_cache_hits == 0:
                        cold.append(st.time_s)
        return cold, hit

    cold, none_hit = replay(False)
    assert not none_hit
    _, hit = replay(CacheConfig(capacity_bytes=32 << 20))
    assert hit, "the cache never engaged"
    assert _pctl(cold, 0.50) >= 5.0 * _pctl(hit, 0.50)


def test_scheduler_writeback_lane_drains_in_flush_windows():
    s = _store(cache=CacheConfig(write_back=True))
    sched = BatchScheduler(s)
    put = sched.submit_put("u", [("f", _data(80_000, seed=65))])
    sched.flush()
    assert put.ok
    assert s.cache.dirty_count == 0  # the write-back lane ran
    assert sched.stats.n_writeback_windows >= 1
    assert sched.stats.writeback_chunks > 0
    got, _ = s.get_file("u", "f")
    assert got == _data(80_000, seed=65)


def test_scheduler_writeback_lane_respects_per_flush_budget():
    s = _store(cache=CacheConfig(write_back=True))
    sched = BatchScheduler(s, writeback_bytes_per_flush=1)
    for i in range(3):
        sched.submit_put("u", [(f"f{i}", _data(60_000, seed=70 + i))])
    sched.flush()
    assert s.cache.dirty_count > 0  # bounded window left a backlog
    while s.cache.dirty_count:
        before = s.cache.dirty_count
        sched.flush()  # empty-queue flush still advances the lane
        assert s.cache.dirty_count < before
    for i in range(3):
        got, _ = s.get_file("u", f"f{i}")
        assert got == _data(60_000, seed=70 + i)


# ------------------------------------------------- differential proofs ----

LIFE = dict(add_shard_at=8, drain_shard_at=16)


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
@pytest.mark.parametrize("shards", [1, 2])
def test_cache_differential_direct(engine, shards):
    cfg = ShardTraceConfig(**(LIFE if shards > 1 else {}))
    run_cache_differential(cfg, shards=shards, engine=engine)


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
@pytest.mark.parametrize("flush_every", [1, 4])
def test_cache_differential_scheduler(engine, flush_every):
    run_cache_differential(ShardTraceConfig(**LIFE), shards=2,
                           engine=engine, mode="scheduler",
                           flush_every=flush_every)


def test_cache_differential_read_only_cache():
    run_cache_differential(ShardTraceConfig(), write_back=False)


def test_cache_differential_tiny_capacity_thrashes_but_stays_exact():
    run_cache_differential(ShardTraceConfig(), capacity_bytes=32 << 10)
