"""Failure-storm repair subsystem: fault-injection harness + contracts.

Four contract families for ``repro.core.repair.RepairManager``:

* **storm recovery (differential)** -- after any seeded kill / revive /
  replace / repair schedule from ``workload.failure_storm_trace``, every
  file whose referenced chunks kept >= k surviving pieces reads back
  byte-identical, on both engines (a hypothesis property plus a seeded
  loop).
* **accounting** -- a repair pass never aborts: every chunk copy lands in
  exactly one of rebuilt / skipped-healthy / unrecoverable, and the piece
  ledger balances (``pieces_missing == rebuilt + failed + unrecoverable``).
* **launch counts** -- a storm over C clusters drains as cross-cluster
  sub-batches costing O(length buckets) decode+encode launches per
  sub-batch, never O(chunks) (the CI launch-count regression lane).
* **integration** -- degraded reads feed the read-repair queue; the
  ``BatchScheduler`` repair lane drains it in bounded windows between
  user flushes; ``StorageNode.put`` rejects conflicting re-puts so a
  repair bug can never silently corrupt pieces.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cluster import Cluster, PieceConflictError, StorageNode
from repro.core.repair import RepairManager
from repro.core.store import SEARSStore
from repro.core.workload import (StormConfig, apply_storm,
                                 failure_storm_trace)

ENGINES = ["numpy", "kernel", "fused"]


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.int64).astype(np.uint8).tobytes()


def _store(engine="numpy", **kw):
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    return SEARSStore(n=10, k=5, binding="ulb", engine=engine, **kw)


def _populate(store, n_users=3, files_per_user=3, size=35_000):
    files = {}
    for u in range(n_users):
        user = f"user{u}"
        fs = [(f"u{u}/f{i}", _data(size + 512 * i, seed=u * 16 + i))
              for i in range(files_per_user)]
        store.put_files(user, fs)
        files[user] = fs
    return files


def _data_clusters(store):
    return [c for c in store.clusters if c.used > 0]


# ------------------------------------------------------- node/cluster ------
def test_node_put_conflicting_bytes_raises():
    """Silent-idempotency fix: a re-put with different bytes must raise."""
    node = StorageNode(node_id=0, capacity=1 << 20)
    node.put(b"\x01" * 20, 0, b"abc")
    node.put(b"\x01" * 20, 0, b"abc")  # byte-identical re-put: idempotent
    assert node.used == 3
    with pytest.raises(PieceConflictError):
        node.put(b"\x01" * 20, 0, b"XYZ")
    assert node.get(b"\x01" * 20, 0) == b"abc"  # original piece untouched


def test_replace_nodes_come_back_empty():
    cluster = Cluster(cluster_id=0, n=4, node_capacity=1 << 20)
    cluster.store_chunk(b"\x02" * 20, [b"p0", b"p1", b"p2", b"p3"])
    cluster.kill_nodes([1])
    cluster.replace_nodes([1])
    assert cluster.nodes[1].alive and cluster.nodes[1].used == 0
    assert not cluster.nodes[1].has(b"\x02" * 20, 1)
    assert cluster.nodes[0].has(b"\x02" * 20, 0)  # neighbours untouched


def test_piece_census_classifies_every_slot():
    cluster = Cluster(cluster_id=0, n=5, node_capacity=1 << 20)
    cid = b"\x03" * 20
    cluster.store_chunk(cid, [b"a", b"b", b"c", b"d", b"e"])
    cluster.kill_nodes([0])      # dead, piece intact: neither bucket
    cluster.replace_nodes([2])   # alive, piece gone: rebuild target
    health = cluster.piece_census([cid])[cid]
    assert health.holders == (1, 3, 4)
    assert health.missing == (2,)
    assert not health.whole and health.recoverable(3)
    cluster.revive_nodes([0])
    health = cluster.piece_census([cid])[cid]
    assert health.holders == (0, 1, 3, 4)  # revived holder serves again


# ------------------------------------------------------- prioritization ----
def test_scan_prioritizes_fewest_survivors_first():
    s = _store()
    _populate(s, n_users=2, files_per_user=2)
    mild, severe = _data_clusters(s)[:2]
    mild.replace_nodes([0])            # 9 survivors
    severe.replace_nodes([0, 1, 2])    # 7 survivors: most at risk
    s.repair.scan()
    items = sorted(s.repair._pending.values(), key=lambda it: it.priority)
    n_severe = len(s.index.cluster_chunks(severe.cluster_id))
    assert all(it.cluster_id == severe.cluster_id for it in items[:n_severe])
    assert items[0].n_survivors < items[-1].n_survivors


def test_repair_skips_healthy_chunks_without_data_plane_work():
    s = _store()
    _populate(s)
    report = s.repair_all()
    assert not report.rebuilt and not report.unrecoverable
    assert len(report.skipped_healthy) == len(s.index)
    assert report.n_sub_batches == 0  # no decode/encode for whole chunks
    assert s.repair.pending == 0


# ---------------------------------------------------------- accounting -----
def test_unrecoverable_recorded_not_raised_partial_progress_kept():
    """An unrecoverable chunk must not abort the pass: recoverable
    neighbours (even in other clusters) are still rebuilt and the report
    accounts for everything."""
    s = _store()
    files = _populate(s, n_users=2, files_per_user=2)
    lost_cluster, ok_cluster = _data_clusters(s)[:2]
    lost_cluster.kill_nodes([0, 1, 2, 3, 4, 5])
    lost_cluster.replace_nodes([0, 1, 2, 3, 4, 5])  # 4 holders < k: lost
    ok_cluster.replace_nodes([0, 1])                # 8 holders: repairable

    report = s.repair_all()  # must not raise
    lost_ids = s.index.cluster_chunks(lost_cluster.cluster_id)
    ok_ids = s.index.cluster_chunks(ok_cluster.cluster_id)
    assert {cid for cid, _ in report.unrecoverable} == lost_ids
    assert {cid for cid, _ in report.rebuilt} == ok_ids
    assert report.balanced
    assert report.pieces_rebuilt == 2 * len(ok_ids)
    assert report.pieces_unrecoverable == 6 * len(lost_ids)
    # partial progress is real: the repaired cluster's files survive a
    # fresh n-k failure wave
    ok_cluster.kill_nodes([2, 3, 4, 5, 6])
    user = next(u for u, fs in files.items()
                if any(cl == ok_cluster.cluster_id
                       for cl, _ in [(e[1], 0) for e in
                                     s.switching[u].get_meta(fs[0][0]).entries]))
    for fn, blob in files[user]:
        assert s.get_file(user, fn)[0] == blob


def test_stale_hint_healed_by_node_death_reported_exactly_once():
    """A hinted chunk that turns whole again (its empty replacement died)
    must appear exactly once in skipped_healthy -- scan() drops the stale
    queue entry instead of letting drain() re-census and double-count."""
    s = _store()
    s.put_file("u", "f", _data(30_000, seed=14))
    cluster = _data_clusters(s)[0]
    cluster.kill_nodes([0])
    cluster.replace_nodes([0])
    s.get_file("u", "f")  # degraded read queues every chunk
    n_copies = len(s.index)
    assert s.repair.pending == len(s.index.cluster_chunks(
        cluster.cluster_id))
    cluster.kill_nodes([0])  # empty replacement dies: chunks whole again
    report = s.repair_all()
    assert report.n_chunks == n_copies  # each copy in exactly one bucket
    assert len(report.skipped_healthy) == n_copies
    assert len(set(report.skipped_healthy)) == n_copies  # no duplicates
    assert s.repair.pending == 0


def test_all_writes_failed_reports_failed_not_healthy():
    """A decodable chunk whose every rebuild write fails must land in
    ``failed`` (still degraded, retried later) -- never in
    ``skipped_healthy``."""
    s = _store()
    s.put_file("u", "f", _data(30_000, seed=12))
    cluster = _data_clusters(s)[0]
    cluster.kill_nodes([0])
    cluster.replace_nodes([0])
    cluster.nodes[0].capacity = 0  # replacement too small: writes fail
    report = s.repair_all()
    cids = s.index.cluster_chunks(cluster.cluster_id)
    assert {cid for cid, _ in report.failed} == cids
    assert not report.rebuilt and not report.skipped_healthy
    assert report.pieces_failed == len(cids) and report.balanced
    assert len(report.errors) == len(cids)
    # the chunk is genuinely still degraded and a fresh scan re-finds it
    s.repair.scan()
    assert s.repair.pending == len(cids)


def test_repair_cluster_stays_scoped_to_its_cluster():
    """repair_cluster(X) must not drain other clusters' queued hints nor
    count their pieces in its return value."""
    s = _store()
    _populate(s, n_users=2, files_per_user=2)
    a, b = _data_clusters(s)[:2]
    a.replace_nodes([0])
    b.replace_nodes([0, 1])
    s.repair.scan()  # both clusters queued
    a_ids = s.index.cluster_chunks(a.cluster_id)
    rebuilt = s.repair_cluster(a.cluster_id)
    assert rebuilt == len(a_ids)  # only cluster A's pieces
    # cluster B untouched: still queued, still degraded
    assert s.repair.pending == len(s.index.cluster_chunks(b.cluster_id))
    census = b.piece_census(sorted(s.index.cluster_chunks(b.cluster_id)))
    assert all(not h.whole for h in census.values())


def test_safe_trace_keeps_k_survivors_at_every_moment():
    """Safe-mode cap must hold even when replacements are killed and then
    revived (a revived ex-replacement comes back empty, not healed) --
    with no repair events at all, every chunk keeps >= k holders."""
    for seed in range(6):
        s = _store()
        _populate(s, n_users=2, files_per_user=1, size=15_000)
        cfg = StormConfig(n_clusters=len(s.clusters), n_steps=5,
                          storm_clusters=4, kills_per_storm=3,
                          revive_prob=0.8, replace_fraction=0.5,
                          repair_every_step=False, seed=seed)
        for ev in failure_storm_trace(cfg):
            apply_storm(s, [ev])
            for cluster in s.clusters:
                cids = sorted(s.index.cluster_chunks(cluster.cluster_id))
                for cid, h in cluster.piece_census(cids).items():
                    assert len(h.holders) >= s.k, \
                        f"seed {seed}: chunk below k survivors mid-trace"


def test_repair_cluster_thin_wrapper_back_compat():
    s = _store()
    s.put_file("u", "f", _data(60_000, seed=3))
    cluster = _data_clusters(s)[0]
    cluster.kill_nodes([1, 3])
    cluster.replace_nodes([1, 3])
    rebuilt = s.repair_cluster(cluster.cluster_id)
    assert isinstance(rebuilt, int) and rebuilt > 0
    # an unrecoverable cluster reports 0 instead of raising mid-pass
    cluster.kill_nodes([0, 2, 4, 5, 6, 7])
    assert s.repair_cluster(cluster.cluster_id) == 0


def test_repair_restores_full_survivability():
    s = _store()
    files = _populate(s)
    for c in _data_clusters(s):
        c.kill_nodes([0, 4])
        c.replace_nodes([0, 4])
    report = s.repair_all()
    assert report.balanced and not report.unrecoverable
    for c in _data_clusters(s):  # back to full strength: survive n-k fresh
        c.kill_nodes([1, 2, 5, 6, 8])
    for user, fs in files.items():
        for (fn, blob), (out, _) in zip(
                fs, s.get_files(user, [fn for fn, _ in fs])):
            assert out == blob


# ---------------------------------------------------------- read-repair ----
def test_degraded_get_feeds_read_repair_queue():
    s = _store()
    s.put_file("u", "f", _data(45_000, seed=7))
    cluster = _data_clusters(s)[0]
    cluster.kill_nodes([0])
    cluster.replace_nodes([0])  # systematic piece 0 lost -> degraded reads
    blob, _ = s.get_file("u", "f")
    assert blob == _data(45_000, seed=7)
    entries = {cid for cid, _ in s.switching["u"].get_meta("f").entries}
    assert s.repair.pending == len(entries)
    report = s.repair.drain()
    assert {cid for cid, _ in report.rebuilt} == entries
    assert s.repair.pending == 0
    health = cluster.piece_census(sorted(entries))
    assert all(h.whole for h in health.values())


def test_hint_on_merely_down_holder_is_dropped():
    """A read that went non-systematic only because a holder is *down*
    (piece intact, no alive rebuild target) must not queue busywork."""
    s = _store()
    s.put_file("u", "f", _data(25_000, seed=8))
    _data_clusters(s)[0].kill_nodes([2])
    s.get_file("u", "f")
    assert s.repair.pending == 0


# ------------------------------------------------------ scheduler lane -----
def test_scheduler_repair_lane_bounded_and_interleaved():
    s = _store()
    files = _populate(s, n_users=2, files_per_user=2)
    for c in _data_clusters(s):
        c.replace_nodes([0, 1])
    s.repair.scan()
    backlog = s.repair.pending
    assert backlog > 8
    sched = s.scheduler()
    sched.repair_chunks_per_flush = 4  # bounded: foreground never starves
    req = sched.submit_put("fresh", [("g", _data(20_000, seed=9))])
    sched.flush()
    assert req.ok
    assert sched.stats.n_repair_windows == 1
    assert sched.stats.repair_chunks == 4  # exactly the per-flush budget
    assert s.repair.pending == backlog - 4
    while s.repair.pending:  # idle flushes keep draining the backlog
        sched.flush()
    assert sched.stats.repair_pieces_rebuilt == 2 * backlog
    assert sched.stats.repair_seconds > 0
    for user, fs in files.items():
        for (fn, blob), (out, _) in zip(
                fs, s.get_files(user, [fn for fn, _ in fs])):
            assert out == blob


def test_repair_lane_launch_accounting_separate_from_foreground():
    s = _store(engine="kernel", num_clusters=2)
    s.put_files("u", [(f"f{i}", _data(30_000, seed=20 + i))
                      for i in range(3)])
    cluster = _data_clusters(s)[0]
    cluster.replace_nodes([6, 7])  # parity pieces lost: decode stays
    s.repair.scan()                # systematic, encode must still launch
    sched = s.scheduler()
    sched.repair_chunks_per_flush = 256
    sched.submit_put("v", [("g", _data(25_000, seed=30))])
    sched.flush()
    assert sched.stats.repair_gf_launches > 0
    assert sched.stats.gf_launches > 0  # foreground counted separately
    before = sched.stats.repair_gf_launches
    sched.submit_put("w", [("h", _data(25_000, seed=31))])
    sched.flush()  # queue empty: no repair window, counter frozen
    assert sched.stats.repair_gf_launches == before
    assert sched.stats.n_repair_windows == 1


# ------------------------------------------------- launch-count lane -------
def test_storm_repair_launch_counts_stay_o_buckets():
    """A storm over C clusters drains in cross-cluster sub-batches of
    O(length buckets) decode + encode launches -- never O(chunks)."""
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="kernel")
    _populate(s, n_users=3, files_per_user=4, size=30_000)
    clusters = _data_clusters(s)
    for c in clusters:
        c.kill_nodes([0, 1])      # forces non-systematic decodes
        c.replace_nodes([2, 3])   # two rebuild targets per chunk
    before = LAUNCHES.snapshot()
    report = s.repair_all()
    delta = LAUNCHES.delta(before)
    n_chunks = len(report.rebuilt)
    assert n_chunks > 30  # enough work that O(chunks) would be obvious
    assert report.n_sub_batches == 1  # cross-cluster: ONE window for all
    assert delta.gf <= 16, f"repair re-serialized: {delta.gf} GF launches"
    assert delta.gf < n_chunks
    assert delta.sha1 == 0 and delta.gear == 0  # repair never re-hashes


def test_repair_sub_batch_launches_scale_with_windows_not_chunks():
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="kernel")
    _populate(s, n_users=2, files_per_user=3, size=30_000)
    for c in _data_clusters(s):
        c.replace_nodes([0, 5])
    manager = RepairManager(s, sub_batch=8)
    manager.scan()
    queued = manager.pending
    before = LAUNCHES.snapshot()
    report = manager.drain()
    delta = LAUNCHES.delta(before)
    assert report.n_sub_batches == -(-queued // 8)
    assert delta.gf <= 16 * report.n_sub_batches


@pytest.mark.parametrize("engine", ["numpy", "kernel"])
def test_repair_artifacts_do_not_depend_on_sub_batch_size(engine):
    """One storm rebuilt chunk by chunk (sub_batch=1) and in batched
    cross-cluster sub-batches leaves byte-identical nodes, the same
    balanced ledger and every file readable; on the device engine the
    per-chunk pass pays at least one GF launch per chunk, the batched
    one at most 16 per sub-batch."""
    from repro.kernels.launches import LAUNCHES

    storm = failure_storm_trace(StormConfig(
        n_clusters=6, n_steps=2, storm_clusters=6, kills_per_storm=2,
        revive_prob=1.0, replace_fraction=1.0, repair_every_step=False,
        seed=17))
    runs = []
    for sub_batch in (1, 64):
        s = _store(engine=engine, num_clusters=6)
        files = _populate(s, n_users=4, files_per_user=3, size=30_000)
        apply_storm(s, storm)
        before = LAUNCHES.snapshot()
        report = RepairManager(s, sub_batch=sub_batch).repair()
        runs.append((s, report, LAUNCHES.delta(before).gf))
    (s1, per_chunk, gf1), (s64, batched, gf64) = runs
    assert per_chunk.balanced and batched.balanced
    assert not per_chunk.unrecoverable and not batched.unrecoverable
    assert per_chunk.pieces_rebuilt == batched.pieces_rebuilt > 0
    for c1, c64 in zip(s1.clusters, s64.clusters):
        for n1, n64 in zip(c1.nodes, c64.nodes):
            assert n1._pieces == n64._pieces
    for user, fs in files.items():
        for store in (s1, s64):
            for (fn, blob), (out, _) in zip(
                    fs, store.get_files(user, [fn for fn, _ in fs])):
                assert out == blob, f"{user}/{fn} corrupted"
    if engine == "kernel":
        assert gf1 >= len(per_chunk.rebuilt)
        assert gf64 <= 16 * batched.n_sub_batches


# ------------------------------------------- storm differential harness ----
def _storm_roundtrip(engine: str, seed: int) -> None:
    """Safe storm: every file must read back byte-identical afterwards."""
    s = _store(engine=engine)
    files = _populate(s, n_users=2, files_per_user=2, size=25_000)
    cfg = StormConfig(n_clusters=len(s.clusters), n_steps=3,
                      storm_clusters=3, kills_per_storm=2,
                      revive_prob=0.7, replace_fraction=0.6, seed=seed)
    reports = apply_storm(s, failure_storm_trace(cfg))
    assert reports, "safe trace must include repair passes"
    for rep in reports:
        assert rep.balanced, "repair ledger unbalanced"
        assert not rep.unrecoverable, "safe storm may not lose data"
        assert rep.pieces_missing == rep.pieces_rebuilt
    for user, fs in files.items():
        for (fn, blob), (out, _) in zip(
                fs, s.get_files(user, [fn for fn, _ in fs])):
            assert out == blob, f"{user}/{fn} corrupted by storm"


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_storm_roundtrip_seeded(engine, seed):
    """Seeded fallback harness (always runs, hypothesis or not)."""
    _storm_roundtrip(engine, seed)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_storm_roundtrip_property(seed):
    """Property form: any safe storm schedule is fully recoverable."""
    _storm_roundtrip("numpy", seed)


@pytest.mark.parametrize("engine", ENGINES)
def test_lossy_storm_differential(engine):
    """allow_data_loss storms: files over >= k-survivor clusters read
    back byte-identical after repair; chunks pushed below k survivors are
    reported unrecoverable and their files raise on retrieval."""
    s = _store(engine=engine)
    files = _populate(s, n_users=3, files_per_user=2, size=25_000)
    cfg = StormConfig(n_clusters=len(s.clusters), n_steps=3,
                      storm_clusters=4, kills_per_storm=4,
                      revive_prob=0.5, replace_fraction=0.8,
                      repair_every_step=False, allow_data_loss=True, seed=5)
    apply_storm(s, failure_storm_trace(cfg))
    report = s.repair_all()
    assert report.balanced
    unrecoverable = set(report.unrecoverable)

    for user, fs in files.items():
        for fn, blob in fs:
            entries = s.switching[user].get_meta(fn).entries
            broken = [e for e in entries if e in unrecoverable]
            if broken:
                with pytest.raises(ValueError):
                    s.get_file(user, fn)
                continue
            # every referenced chunk kept >= k survivors: must be whole
            # again after the pass, and the bytes must be exact
            out, _ = s.get_file(user, fn)
            assert out == blob, f"{user}/{fn} corrupted"
    # report accounts for every chunk that is below k survivors right now
    for cluster in s.clusters:
        cids = sorted(s.index.cluster_chunks(cluster.cluster_id))
        census = cluster.piece_census(cids)
        for cid in cids:
            below_k = len(census[cid].holders) < s.k
            assert ((cid, cluster.cluster_id) in unrecoverable) == below_k
