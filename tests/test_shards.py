"""Sharded control plane: ShardMap mechanics, the N-shard-vs-1-shard
differential proof (all engines, direct and scheduler paths, mid-trace
add/drain), lifecycle edges, and per-shard launch economics."""

import hashlib

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from differential import (artifacts, assert_identical, assert_shard_balance,
                          build_store, replay, run_differential)
from repro.core.shard import N_BUCKETS, ShardMap
from repro.core.store import SEARSStore
from repro.core.workload import ShardTraceConfig, multi_shard_trace


def _blob(seed, n=24 << 10):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.int64).astype(np.uint8).tobytes()


# ------------------------------------------------------ ShardMap mechanics --

def test_shardmap_ownership_is_deterministic_and_fair():
    for n in (1, 2, 4, 7):
        a, b = ShardMap(n), ShardMap(n)
        assert a.topology() == b.topology()
        counts: dict[int, int] = {}
        for o in a._owner:
            counts[o] = counts.get(o, 0) + 1
        assert counts == a._want()
        assert sum(counts.values()) == N_BUCKETS


def test_shardmap_bounds():
    with pytest.raises(ValueError):
        ShardMap(0)
    with pytest.raises(ValueError):
        ShardMap(N_BUCKETS + 1)


def test_add_drain_accounting_and_monotonic_ids():
    m = ShardMap(2)
    assert m.live_ids() == [0, 1]
    s2 = m.add_shard()
    assert s2.shard_id == 2
    counts: dict[int, int] = {}
    for o in m._owner:
        counts[o] = counts.get(o, 0) + 1
    assert counts == m._want()  # newcomer stole its fair share
    m.drain_shard(0)
    assert m.live_ids() == [1, 2]
    assert all(o in (1, 2) for o in m._owner)
    s3 = m.add_shard()
    assert s3.shard_id == 3  # retired ids are never reused
    with pytest.raises(KeyError):
        m.drain_shard(0)


def test_drain_last_shard_refuses():
    m = ShardMap(1)
    with pytest.raises(ValueError):
        m.drain_shard(m.live_ids()[0])


def test_lifecycle_migrates_bucket_state():
    m = ShardMap(1)
    cids = [bytes([b]) + b"\x00" * 19 for b in range(0, 256, 17)]
    users = [f"user{i}" for i in range(8)]
    home = m.shards[0]
    for cid in cids:
        home.index.add(cid, 0, 100)
    for u in users:
        home.tables[u] = f"table-{u}"
        home.bound.setdefault("standard", {})[u] = 3
    m.add_shard()
    m.add_shard()
    for cid in cids:  # every key lives with its current bucket owner
        owner = m.shard_of_chunk(cid)
        assert cid in owner.index._chunks
    for u in users:
        owner = m.shard_of_user(u)
        assert owner.tables[u] == f"table-{u}"
        assert owner.bound["standard"][u] == 3
    m.drain_shard(1)
    for cid in cids:
        assert cid in m.shard_of_chunk(cid).index._chunks
    for u in users:
        assert m.shard_of_user(u).tables[u] == f"table-{u}"
    assert sum(len(m.shards[s].index) for s in m.live_ids()) == len(cids)


# --------------------------------------------------- differential proofs ----

LIFE = dict(add_shard_at=8, drain_shard_at=16)  # one add + one drain mid-trace


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
@pytest.mark.parametrize("shards", [2, 4])
def test_differential_direct(engine, shards):
    run_differential(ShardTraceConfig(**LIFE), shards=shards, engine=engine)


@pytest.mark.parametrize("engine", ["numpy", "kernel", "fused"])
@pytest.mark.parametrize("flush_every", [1, 4])
def test_differential_scheduler(engine, flush_every):
    # flush_every=1: one window per flush, no put window begun ahead;
    # 4: flushes carry several put windows, each next one begun ahead
    run_differential(ShardTraceConfig(**LIFE), shards=4, engine=engine,
                     mode="scheduler", flush_every=flush_every)


def test_single_shard_degenerate_matches_legacy_default():
    """shards=1 is the legacy store, same code path, byte for byte."""
    ops = multi_shard_trace(ShardTraceConfig())
    legacy = build_store()
    legacy_obs = replay(legacy, ops, lifecycle=False)
    one = build_store(shards=1)
    one_obs = replay(one, ops, lifecycle=False)
    assert_identical((legacy_obs, artifacts(legacy)),
                     (one_obs, artifacts(one)))


# ------------------------------------------------------- lifecycle edges ----

def _window_requests(tag):
    from repro.core.scheduler import PUT, Request
    return [Request(request_id=i, user=u, kind=PUT,
                    files=[(f"{u}/{tag}{j}", _blob(i * 7 + j))
                           for j in range(2)])
            for i, u in enumerate(("alice", "bob", "carol", "dave"))]


def _commit_window(store, reqs):
    store._batch_put(reqs)
    for r in reqs:
        assert r.error is None, r.error


@pytest.mark.parametrize("event", ["add", "drain"])
def test_lifecycle_during_active_flush_window(event):
    """A shard add/drain landing between a put window's begin and finish
    commits byte-identically: the demux was captured at begin, and all
    control-plane writes route through the *current* topology."""
    base = build_store(shards=3)
    _commit_window(base, _window_requests("w"))

    subj = build_store(shards=3)
    reqs = _window_requests("w")
    state = subj._put_window_begin(reqs)
    if event == "add":
        subj.add_shard()
    else:
        subj.drain_shard(subj.shard_map.live_ids()[0])
    subj._put_window_finish(state)
    for r in reqs:
        assert r.error is None, r.error

    assert_identical(([], artifacts(base)), ([], artifacts(subj)))
    assert_shard_balance(subj)
    for r in reqs:
        for fn, blob in r.files:
            out, _ = subj.get_file(r.user, fn)
            assert out == blob


def test_drained_shard_is_retired_and_stale_state_inert():
    """A drained shard's id is never reused; stale writes to the drained
    object can't reach routing, the ledger, or a later newcomer."""
    s = build_store(shards=2)
    s.put_files("alice", [("a", _blob(1))])
    victim = s.shard_map.live_ids()[0]
    stale = s.shard_map.shards[victim]
    old_live = s.shard_map.live_ids()
    s.drain_shard(victim)
    assert stale.empty()  # drain migrated everything off it
    # forge stale metadata on the retired object (a zombie holding a ref)
    stale.tables["ghost"] = object()
    stale.index.add(b"\xff" * 20, 0, 10)
    new_id = s.add_shard()
    assert new_id > max(old_live)  # fresh id, not the retired one
    # the newcomer inherits only legitimately migrated bucket state --
    # the zombie's forged entries are unreachable from the live topology
    assert b"\xff" * 20 not in s.index
    assert "ghost" not in s.switching
    out, _ = s.get_file("alice", "a")
    assert out == _blob(1)
    assert_shard_balance(s)  # zombie state never entered the ledger


def test_sears_shards_env_default(monkeypatch):
    monkeypatch.setenv("SEARS_SHARDS", "4")
    assert len(SEARSStore(n=4, k=2, num_clusters=2).shard_map) == 4
    # explicit kwarg beats the env default
    assert len(SEARSStore(n=4, k=2, num_clusters=2, shards=2).shard_map) == 2
    monkeypatch.delenv("SEARS_SHARDS")
    assert len(SEARSStore(n=4, k=2, num_clusters=2).shard_map) == 1


# ------------------------------------------------- property-based edges ----

@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from(["add", "drain"]), max_size=12),
       st.integers(min_value=1, max_value=6))
def test_property_lifecycle_keeps_ownership_fair(ops, start):
    m = ShardMap(start)
    for op in ops:
        if op == "add" and len(m) < 8:
            m.add_shard()
        elif op == "drain" and len(m) > 1:
            m.drain_shard(m.live_ids()[0])
    counts: dict[int, int] = {}
    for o in m._owner:
        counts[o] = counts.get(o, 0) + 1
    assert counts == m._want()


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=2, max_value=5))
def test_property_random_traces_are_shard_invariant(seed, shards):
    cfg = ShardTraceConfig(n_ops=10, seed=seed, add_shard_at=3,
                           drain_shard_at=7)
    run_differential(cfg, shards=shards)


# ------------------------------------------------- launch economics ----

def test_per_shard_window_launch_economics():
    """A sharded flush window's data-plane launches stay O(code buckets x
    length buckets) per shard sub-window -- one hash batch per group,
    never per chunk."""
    s = build_store(engine="kernel", shards=4)
    sched = s.scheduler()
    users = [f"user{i}" for i in range(6)]
    n_chunks_in = 0
    for i, u in enumerate(users):
        files = [(f"{u}/f{j}", _blob(100 + i * 7 + j, n=48 << 10))
                 for j in range(3)]
        sched.submit_put(u, files)
    n_groups = len(s.window_shards(users))
    assert n_groups > 1  # the trace actually exercises the demux
    sched.flush()
    stats = sched.stats
    assert stats.n_put_windows == 1
    assert stats.n_shard_subwindows == n_groups
    assert stats.sha1_launches == n_groups  # one hash batch per sub-window
    n_chunks = s.stats().n_unique_chunks
    assert n_chunks > 4 * n_groups
    # encode launches: per-(code, length-bucket) per group, not per chunk
    assert stats.gf_launches < n_chunks
