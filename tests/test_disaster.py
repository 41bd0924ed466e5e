"""Disaster recovery: cluster loss, re-placement, scrubbing, throttling.

Five contract families layered on top of ``tests/test_repair.py``:

* **census matrix** -- ``Cluster.piece_census`` classifies every
  (chunk, node) slot consistently across kill / revive / replace /
  declare-lost: replaced (wiped) and declared-lost nodes are *never*
  holders; down-and-empty slots surface as ``lost``.
* **re-placement** -- after ``declare_cluster_lost``, chunks with >= k
  surviving pieces cluster-wide rebuild onto a healthy pool cluster
  (byte-identical retrieval, balanced replace ledger, metadata moved
  atomically); chunks without enough survivors are honestly
  unrecoverable; when no fresh target is viable the move degrades to a
  metadata-only merge onto a healthy donor copy.
* **throttling** -- a ``RepairBandwidth`` token bucket defers drain items
  beyond the budget (they stay queued, strict priority order) and feeds
  the per-cluster utilisation foreground reads are charged.
* **scrub lane** -- ``BatchScheduler(scrub_interval=...)`` runs sampled
  censuses off an injectable clock; damage is found and healed without
  any foreground read tripping over it.
* **storm differentials** -- seeded (and hypothesis, where installed)
  cluster-loss storms on all three engines under ``SEARS_SANITIZE``:
  safe-mode traces end with every file byte-identical and every ledger
  balanced; re-placement drains stay O(code buckets x length buckets)
  launches per sub-batch.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cluster import Cluster, NodeDownError
from repro.core.latency import RepairBandwidth, calibrate
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
    kw.setdefault("sanitize", True)
    return SEARSStore(n=10, k=5, binding="ulb", engine=engine, **kw)


def _populate_with_duplicates(store, n_users=2, files_per_user=3,
                              size=20_000):
    """Every user uploads the SAME files: under ULB each user's copy
    lands on their own bound cluster, so cross-cluster duplicate copies
    exist -- the donor set cluster-loss re-placement decodes from."""
    files = [(f"f{i}", _data(size + 512 * i, seed=i))
             for i in range(files_per_user)]
    for u in range(n_users):
        store.put_files(f"user{u}", files)
    return files


# ----------------------------------------------------- census matrix ------
def test_census_matrix_kill_revive_replace_lost():
    """Every (state, slot) cell of the kill/revive/replace/lost matrix."""
    cid = b"\x03" * 20
    cluster = Cluster(cluster_id=0, n=6, node_capacity=1 << 20, k=3)
    cluster.store_chunk(cid, [bytes([i]) * 8 for i in range(6)])

    h = cluster.piece_census([cid])[cid]
    assert h.holders == (0, 1, 2, 3, 4, 5) and h.missing == () \
        and h.lost == ()

    cluster.kill_nodes([0])       # down, piece intact: none of the three
    cluster.replace_nodes([1])    # alive but empty: missing
    cluster.kill_nodes([2])
    cluster.replace_nodes([2])
    cluster.kill_nodes([2])       # replaced then killed again: lost
    h = cluster.piece_census([cid])[cid]
    assert h.holders == (3, 4, 5)
    assert h.missing == (1,)
    assert h.lost == (2,)
    assert not h.whole and h.recoverable(cluster.k)

    cluster.revive_nodes([0])     # revive with pieces intact: holder again
    h = cluster.piece_census([cid])[cid]
    assert h.holders == (0, 3, 4, 5) and h.lost == (2,)

    cluster.declare_lost()
    h = cluster.piece_census([cid])[cid]
    assert h.holders == () and h.missing == ()
    assert h.lost == (0, 1, 2, 3, 4, 5)
    assert h.whole and not h.recoverable(cluster.k)  # the lost signature


def test_declared_lost_cluster_refuses_revive_and_is_not_viable():
    cluster = Cluster(cluster_id=0, n=4, node_capacity=1 << 20, k=2)
    cluster.declare_lost()
    cluster.declare_lost()  # idempotent
    assert cluster.lost and cluster.alive_count() == 0
    assert not cluster.viable()
    with pytest.raises(NodeDownError):
        cluster.revive_nodes([0])
    healthy = Cluster(cluster_id=1, n=4, node_capacity=1 << 20, k=2)
    assert healthy.viable(need_bytes=1 << 10)
    healthy.kill_nodes([0, 1, 2])  # 1 alive < k
    assert not healthy.viable()


# ----------------------------------------- store lifecycle + binding ------
def test_declare_cluster_lost_updates_pool_and_rebinds_users():
    s = _store(engine="numpy")
    files = [("a", _data(12_000, seed=1))]
    s.put_files("user0", files)       # ULB binds user0 to cluster 0
    lost_id = s.binding._bound["user0"]
    tag = s.pool_of(lost_id)
    n_queued = s.declare_cluster_lost(lost_id)
    assert n_queued == s.repair.pending > 0
    assert lost_id not in s.pools[tag]
    assert "user0" not in s.binding._bound  # unbound, not stranded
    # the user's next write re-assigns inside the surviving pool
    s.put_files("user0", [("b", _data(8_000, seed=2))])
    new_home = s.binding._bound["user0"]
    assert new_home != lost_id and new_home in s.pools[tag]


def test_admit_cluster_joins_pool_with_pool_code():
    s = _store(engine="numpy", num_clusters=4)
    fresh = s.admit_cluster()
    assert fresh.cluster_id == 4 and (fresh.n, fresh.k) == (10, 5)
    tag = s.pool_of(fresh.cluster_id)
    assert fresh.cluster_id in s.pools[tag]
    assert s.clusters[fresh.cluster_id] is fresh


def test_last_cluster_of_pool_cannot_be_lost_and_state_is_untouched():
    s = _store(engine="numpy", num_clusters=1)
    s.put_files("user0", [("a", _data(10_000, seed=1))])
    with pytest.raises(RuntimeError, match="admit_cluster"):
        s.declare_cluster_lost(0)
    # the refused declaration must not half-mutate anything
    assert not s.clusters[0].lost and s.pools[s.pool_of(0)] == (0,)
    assert s.get_file("user0", "a")[0] == _data(10_000, seed=1)
    s.admit_cluster()
    s.declare_cluster_lost(0)  # now fine
    assert s.clusters[0].lost


# -------------------------------------------------- re-placement ----------
@pytest.mark.parametrize("engine", ENGINES)
def test_cluster_loss_replacement_roundtrip(engine):
    """100% of a lost cluster's recoverable chunks re-place onto a healthy
    pool cluster; retrieval is byte-identical; the ledger balances."""
    s = _store(engine=engine)
    files = _populate_with_duplicates(s, n_users=2, files_per_user=3)
    lost_id = s.binding._bound["user0"]
    queued = s.declare_cluster_lost(lost_id)
    report = s.repair.repair()
    assert report.balanced
    assert len(report.replaced) == queued  # every queued chunk moved
    assert not report.unrecoverable and not report.replace_failed
    assert report.pieces_replace_targets == report.pieces_replaced > 0
    # the lost cluster keeps no records, pieces, or meta references
    assert not s.index.cluster_chunks(lost_id)
    for cid, old, new in report.replaced:
        assert old == lost_id and new != lost_id
        assert not s.clusters[new].lost
        assert s.pool_of(new) == s.pool_of(lost_id)
    for fn, blob in files:
        got, _ = s.get_file("user0", fn)
        assert got == blob


def test_replacement_prefers_fresh_non_holder_cluster():
    """With viable empty clusters in the pool, re-placement lands the full
    n-piece set on a non-holder (not a metadata merge onto the donor)."""
    s = _store(engine="numpy")
    _populate_with_duplicates(s, n_users=2, files_per_user=2)
    lost_id = s.binding._bound["user0"]
    donor_id = s.binding._bound["user1"]
    s.declare_cluster_lost(lost_id)
    report = s.repair.repair()
    assert report.replaced and report.pieces_replaced > 0
    for cid, old, new in report.replaced:
        assert new not in (lost_id, donor_id)  # fresh target, not the donor
        health = s.clusters[new].piece_census([cid])[cid]
        assert len(health.holders) == s.clusters[new].n  # full redundancy


def test_replacement_merges_when_no_fresh_target_exists():
    """A two-cluster pool with a healthy donor copy: losing one cluster
    leaves no non-holder target, so the move is a metadata-only merge --
    zero launches, zero new pieces, refcounts folded onto the donor."""
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="numpy", num_clusters=2)
    files = _populate_with_duplicates(s, n_users=2, files_per_user=2)
    lost_id = s.binding._bound["user0"]
    donor_id = s.binding._bound["user1"]
    assert lost_id != donor_id
    s.declare_cluster_lost(lost_id)
    before = LAUNCHES.snapshot()
    report = s.repair.repair()
    assert LAUNCHES.delta(before).gf == 0  # metadata only
    assert report.balanced and not report.unrecoverable
    assert report.pieces_replace_targets == 0 == report.pieces_replaced
    assert {new for _, _, new in report.replaced} == {donor_id}
    assert report.n_sub_batches == 0
    for fn, blob in files:
        assert s.get_file("user0", fn)[0] == blob
    # both users' references now share the donor records
    for cid, _, new in report.replaced:
        assert s.index.copies(cid) == (donor_id,)
        assert s.index.get(cid, donor_id).refcount >= 2


def test_unrecoverable_cluster_loss_is_honestly_accounted():
    """Without donor copies a lost cluster's chunks are gone: recorded
    unrecoverable, never silently dropped, ledger still balanced."""
    s = _store(engine="numpy")
    fs = [(f"u/f{i}", _data(15_000 + 512 * i, seed=90 + i))
          for i in range(3)]
    s.put_files("user0", fs)  # unique content: single copy, no donors
    lost_id = s.binding._bound["user0"]
    queued = s.declare_cluster_lost(lost_id)
    report = s.repair.repair()
    assert report.balanced
    assert len(report.unrecoverable) == queued > 0
    assert not report.replaced and not report.rebuilt
    # a lost cluster's missing slots are dead, not alive-missing
    assert report.pieces_missing == 0 == report.pieces_unrecoverable
    with pytest.raises(Exception):
        s.get_file("user0", "u/f0")


def test_scan_requeues_lost_cluster_chunks_for_later_passes():
    """A drain that cannot place (whole pool full of holders, donors
    degraded) leaves the record; a later scan re-queues it."""
    s = _store(engine="numpy")
    _populate_with_duplicates(s, n_users=2, files_per_user=2)
    lost_id = s.binding._bound["user0"]
    donor_id = s.binding._bound["user1"]
    queued = s.declare_cluster_lost(lost_id)
    # degrade the donor below k so the union cannot decode *yet*
    s.clusters[donor_id].kill_nodes([0, 1, 2, 3, 4, 5])
    rep = s.repair.repair()
    assert rep.unrecoverable and not rep.replaced
    assert s.repair.pending == 0
    s.clusters[donor_id].revive_nodes([0, 1, 2, 3, 4, 5])
    rep2 = s.repair.repair()  # scan re-queues, drain now re-places
    assert len(rep2.replaced) == queued
    assert rep2.balanced


# ------------------------------------------------------- throttling -------
def test_throttled_drain_defers_and_preserves_priority():
    now = [0.0]
    bw = RepairBandwidth(link_bps=50e6, limit_bps=40_000, window_s=1.0,
                         clock=lambda: now[0])
    s = _store(engine="numpy", repair_bandwidth=bw)
    _populate_with_duplicates(s, n_users=2, files_per_user=3)
    lost_id = s.binding._bound["user0"]
    queued = s.declare_cluster_lost(lost_id)
    rep = s.repair.repair()
    assert rep.deferred > 0 and s.repair.pending == rep.deferred
    assert bw.deferred >= 1 and bw.taken <= bw.burst_bytes
    done = len(rep.replaced)
    # budget refills with (injected) time; repeated drains finish the job
    for _ in range(40):
        if not s.repair.pending:
            break
        now[0] += 1.0
        r = s.repair.drain()
        done += len(r.replaced)
    assert s.repair.pending == 0
    assert done == queued  # every queued chunk eventually re-placed
    for fn in ("f0", "f1", "f2"):
        s.get_file("user0", fn)


def test_unthrottled_bandwidth_tracks_rho_without_deferring():
    now = [0.0]
    bw = RepairBandwidth(link_bps=1e6, limit_bps=None, clock=lambda: now[0])
    s = _store(engine="numpy", repair_bandwidth=bw)
    _populate_with_duplicates(s, n_users=2, files_per_user=3)
    lost_id = s.binding._bound["user0"]
    s.declare_cluster_lost(lost_id)
    rep = s.repair.repair()
    assert rep.deferred == 0 and s.repair.pending == 0
    assert rep.replaced
    # track-only mode still congests: the clusters repair touched report
    # a non-zero utilisation to foreground retrieval
    touched = {new for _, _, new in rep.replaced}
    assert all(bw.rho(c) > 0 for c in touched)
    assert s.repair.cluster_rho(sorted(touched)[0]) == bw.rho(
        sorted(touched)[0])
    now[0] += 1000.0  # traffic ages out of the window
    assert all(bw.rho(c) == 0.0 for c in touched)


def test_bandwidth_validates_and_rho_is_capped():
    with pytest.raises(ValueError):
        RepairBandwidth(link_bps=0)
    with pytest.raises(ValueError):
        RepairBandwidth(limit_bps=-1.0)
    now = [0.0]
    bw = RepairBandwidth(link_bps=1000.0, window_s=1.0,
                         clock=lambda: now[0])
    bw.note(0, 10_000_000)
    assert bw.rho(0) == 0.95  # congestion floor capped below 1.0
    assert bw.rho(1) == 0.0


# Foreground p99 budget while a lost cluster rebuilds, x the no-repair p99.
SLO_FACTOR = 1.5


def _pctl(xs, q):
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(round(q * (len(ys) - 1))))]


def _foreground_p99(arm: str) -> float:
    """Modelled foreground get p99 under one repair arm (fake clock).

    Four users store the same four files, so each user's copy on its
    own ULB cluster is a donor for the others.  ``unthrottled`` and
    ``throttled`` declare user0's cluster lost and rebuild it; users 1-3
    read their copies for twelve one-second windows.  The link and the
    throttle are sized to this data set (a few hundred KB per cluster
    copy), so an unthrottled rebuild saturates its links inside one
    window while the throttled one (10% of the link) spreads it out.
    """
    now = [0.0]
    bw = RepairBandwidth(link_bps=200e3,
                         limit_bps=20e3 if arm == "throttled" else None,
                         window_s=1.0, clock=lambda: now[0])
    s = _store(num_clusters=6, node_capacity=1 << 30, latency=calibrate(),
               repair_bandwidth=bw)
    files = [(f"f{i}", _data(48 * 1024 + 512 * i, seed=31 + i))
             for i in range(4)]
    for u in range(4):
        s.put_files(f"user{u}", files)
    if arm != "no_repair":
        s.declare_cluster_lost(s.binding._bound["user0"])
        s.repair.repair()  # the throttled arm defers most of the queue
    names = [fn for fn, _ in files]
    times = []
    for _ in range(12):
        for user in ("user1", "user2", "user3"):
            times.extend(st.time_s for _, st in s.get_files(user, names))
        now[0] += 1.0  # next window: the bucket refills, traffic ages
        if arm == "throttled" and s.repair.pending:
            s.repair.drain()
    while s.repair.pending:  # the throttled rebuild still finishes
        now[0] += 1.0
        s.repair.drain()
    for fn, blob in files:
        assert s.get_file("user0", fn)[0] == blob
    return _pctl(times, 0.99)


def test_throttled_rebuild_keeps_foreground_p99_within_slo():
    base = _foreground_p99("no_repair")
    assert _foreground_p99("throttled") <= SLO_FACTOR * base
    # the throttle is load-bearing: the same rebuild in one burst floors
    # rho at its congestion cap on every cluster it touched
    assert _foreground_p99("unthrottled") > SLO_FACTOR * base


# -------------------------------------------------------- scrub lane ------
def test_scrub_sweeps_cursor_through_population_and_enqueues_damage():
    s = _store(engine="numpy")
    _populate_with_duplicates(s, n_users=2, files_per_user=3)
    total = sum(len(s.index.cluster_chunks(c.cluster_id))
                for c in s.clusters)
    s.clusters[s.binding._bound["user0"]].replace_nodes([0, 1])
    # small budget: one sweep sees only a slice...
    rep = s.repair.scrub(budget=2)
    assert 0 < rep.n_censused <= 2 * len(s.classes)
    # ...but consecutive sweeps advance the cursor over everything
    censused = rep.n_censused
    for _ in range(32):
        censused += s.repair.scrub(budget=2).n_censused
    assert censused >= total
    assert s.repair.pending > 0  # the damaged chunks were queued
    drained = s.repair.drain()
    assert drained.rebuilt and drained.balanced


def test_scrub_respects_per_class_budget_dict():
    from repro.core.classes import StorageClass

    s = SEARSStore(num_clusters=4, node_capacity=64 << 20, engine="numpy",
                   sanitize=True,
                   classes=[StorageClass.realtime(),
                            StorageClass.archival()])
    blob = _data(30_000, seed=5)
    s.put_files("u", [("hot", blob)], storage_class="realtime")
    s.put_files("u", [("cold", blob)], storage_class="archival")
    rep = s.repair.scrub(budget={"realtime": 1, "archival": 0})
    assert rep.n_censused == 1
    assert set(rep.per_pool) == {"realtime"}


def test_scheduler_scrub_lane_heals_idle_store_via_injected_clock():
    t = [0.0]
    s = _store(engine="numpy")
    files = _populate_with_duplicates(s, n_users=2, files_per_user=2)
    sched = s.scheduler(clock=lambda: t[0], scrub_interval=10.0,
                        repair_chunks_per_flush=64)
    victim = s.clusters[s.binding._bound["user0"]]
    victim.replace_nodes([0, 1])
    assert sched.poll() == [] and sched.stats.n_scrub_sweeps == 0
    healed = False
    for step in range(1, 40):
        t[0] = 10.0 * step + 0.5
        sched.poll()  # idle store: no foreground traffic at all
        if sched.stats.repair_pieces_rebuilt > 0:
            healed = True
            break
    assert healed and sched.stats.n_scrub_sweeps >= 1
    assert sched.stats.scrub_chunks_censused > 0
    assert sched.stats.scrub_enqueued > 0
    health = victim.piece_census(
        sorted(s.index.cluster_chunks(victim.cluster_id)))
    assert all(h.whole for h in health.values())
    for fn, blob in files:
        assert s.get_file("user0", fn)[0] == blob


def test_scrub_is_metadata_only():
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="kernel")
    _populate_with_duplicates(s, n_users=2, files_per_user=2)
    s.clusters[0].replace_nodes([0])
    before = LAUNCHES.snapshot()
    s.repair.scrub()
    d = LAUNCHES.delta(before)
    assert d.gf == 0 and d.sha1 == 0 and d.gear == 0 and d.fused == 0


# ------------------------------------------------------ launch counts -----
def test_replacement_launch_counts_stay_o_buckets():
    """Re-placing a whole lost cluster costs O(code x length buckets) GF
    launches per sub-batch, never O(chunks) -- same ceiling as in-place
    repair even though every recode targets a *different* cluster."""
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="kernel")
    _populate_with_duplicates(s, n_users=2, files_per_user=4, size=30_000)
    lost_id = s.binding._bound["user0"]
    queued = s.declare_cluster_lost(lost_id)
    assert queued > 20  # enough chunks that O(chunks) would be obvious
    before = LAUNCHES.snapshot()
    report = s.repair.repair()
    delta = LAUNCHES.delta(before)
    assert len(report.replaced) == queued
    assert report.n_sub_batches == 1
    assert delta.gf <= 16, f"re-placement re-serialized: {delta.gf}"
    assert delta.gf < queued
    assert delta.sha1 == 0 and delta.gear == 0


def test_mixed_inplace_and_replacement_share_one_sub_batch():
    from repro.kernels.launches import LAUNCHES

    s = _store(engine="kernel")
    _populate_with_duplicates(s, n_users=2, files_per_user=3, size=30_000)
    lost_id = s.binding._bound["user0"]
    donor_id = s.binding._bound["user1"]
    s.clusters[donor_id].replace_nodes([0, 1])  # in-place lane work
    s.declare_cluster_lost(lost_id)             # re-placement lane work
    before = LAUNCHES.snapshot()
    report = s.repair.repair()
    delta = LAUNCHES.delta(before)
    assert report.rebuilt and report.replaced  # both lanes ran
    assert report.n_sub_batches == 1           # ... in ONE engine window
    assert delta.gf <= 16
    assert report.balanced


# --------------------------------------- cluster-loss storm harness -------
def _disaster_roundtrip(engine: str, seed: int) -> None:
    """Safe cluster-loss storm: duplicated uploads guarantee >= k
    cross-cluster survivors, so every file must read back byte-identical
    after the full trace, with every repair ledger balanced."""
    s = _store(engine=engine)
    files = _populate_with_duplicates(s, n_users=2, files_per_user=2,
                                      size=18_000)
    cfg = StormConfig(n_clusters=len(s.clusters), n_steps=3,
                      storm_clusters=2, kills_per_storm=2,
                      revive_prob=0.6, replace_fraction=0.5,
                      cluster_losses=1, racks=2, rack_storm_prob=0.5,
                      seed=seed)
    events = failure_storm_trace(cfg)
    assert any(ev.kind == "cluster_loss" for ev in events)
    reports = apply_storm(s, events)
    assert reports
    for rep in reports:
        assert rep.balanced
        assert not rep.unrecoverable  # safe mode: donors always suffice
    lost_ids = [ev.cluster_id for ev in events if ev.kind == "cluster_loss"]
    for lost_id in lost_ids:
        assert not s.index.cluster_chunks(lost_id)  # fully re-placed
    for u in range(2):
        for fn, blob in files:
            got, _ = s.get_file(f"user{u}", fn)
            assert got == blob


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cluster_loss_storm_roundtrip_seeded(engine, seed):
    _disaster_roundtrip(engine, seed)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_cluster_loss_storm_roundtrip_property(seed):
    _disaster_roundtrip("numpy", seed)


def test_storm_trace_disaster_extensions_off_means_identical_traces():
    """The disaster knobs default off and must not perturb existing
    seeded schedules (replaying old traces stays reproducible)."""
    base = StormConfig(seed=9, n_steps=4)
    extended = StormConfig(seed=9, n_steps=4, cluster_losses=0, racks=0,
                           rack_storm_prob=0.0)
    assert failure_storm_trace(base) == failure_storm_trace(extended)


def test_rack_wave_respects_safe_cap():
    cfg = StormConfig(n_clusters=3, n=10, k=5, n_steps=6,
                      storm_clusters=1, kills_per_storm=1,
                      racks=2, rack_storm_prob=1.0, seed=4)
    down: dict[int, set] = {c: set() for c in range(cfg.n_clusters)}
    for ev in failure_storm_trace(cfg):
        if ev.kind == "kill":
            down[ev.cluster_id] |= set(ev.node_ids)
            assert len(down[ev.cluster_id]) <= cfg.n - cfg.k
        elif ev.kind in ("revive", "replace"):
            down[ev.cluster_id] -= set(ev.node_ids)
        elif ev.kind == "repair":
            down = {c: set() for c in down}  # replacements healed
