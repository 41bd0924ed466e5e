"""Differential N-shard-vs-1-shard proof harness.

The sharded control plane's contract is *byte identity*: an N-shard
store replaying any trace — through any engine, directly or through
the scheduler's flush windows, including mid-trace shard add/drain — must be
indistinguishable from the 1-shard store on every observable:

* every byte returned by every get (captured per-op during replay);
* every ``RetrievalStats`` (incl. the simulated ``time_s``, which draws
  the store's rng in assembly order — any shard-dependent reordering of
  that stream shows up here);
* the final on-node artifacts: a per-(cluster, node) digest over all
  stored pieces;
* the final metadata: chunk-index records, per-user file listings, and
  ``StoreStats``.

``run_differential`` is the reusable fixture: replay a trace against a
1-shard baseline (lifecycle ops skipped) and an N-shard subject
(lifecycle ops applied), assert everything above is identical, and
check per-shard ledger conservation on the subject.
"""

from __future__ import annotations

import dataclasses
import hashlib

from repro.core.cache import CacheConfig
from repro.core.sanitizer import Sanitizer
from repro.core.store import SEARSStore
from repro.core.workload import ShardTraceConfig, multi_shard_trace

__all__ = [
    "ShardTraceConfig", "multi_shard_trace", "build_store", "replay",
    "artifacts", "assert_identical", "assert_shard_balance",
    "run_differential", "run_cache_differential",
]


def build_store(engine: str = "numpy", shards: int = 1,
                **kw) -> SEARSStore:
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    kw.setdefault("binding", "ulb")
    return SEARSStore(n=10, k=5, engine=engine, shards=shards, **kw)


def _apply_lifecycle(store: SEARSStore, op: tuple) -> None:
    if op[0] == "add_shard":
        store.add_shard()
    else:  # ("drain_shard", rank): rank-th live shard by sorted id
        live = store.shard_map.live_ids()
        store.drain_shard(live[op[1] % len(live)])


def replay(store: SEARSStore, ops: list[tuple], *,
           mode: str = "direct",
           lifecycle: bool = True, flush_every: int = 4,
           with_stats: bool = True) -> list:
    """Run a ``multi_shard_trace`` op list; return the observation log.

    ``mode="direct"`` drives the store API per op; ``mode="scheduler"``
    routes ops through a :class:`BatchScheduler`, flushing every
    ``flush_every`` ops and before any lifecycle op, so add/drain always
    lands between flush windows of the *trace* (the in-window case has
    its own tests).  With ``flush_every=1`` each flush holds one window,
    so no put window is begun ahead; with more, a flush holding several
    put windows issues each next one's chunking pass ahead.
    Lifecycle ops are skipped when ``lifecycle`` is false — the 1-shard
    baseline mode.  ``with_stats=False`` logs only the blob digests —
    the cache differential uses it, since hits legitimately change the
    timing stats while the bytes must not move.
    """
    obs: list = []

    def _observe(blob: bytes, st) -> None:
        digest = hashlib.sha1(blob).hexdigest()
        obs.append((digest, dataclasses.astuple(st)) if with_stats
                   else digest)

    if mode == "direct":
        for op in ops:
            if op[0] in ("add_shard", "drain_shard"):
                if lifecycle:
                    _apply_lifecycle(store, op)
                continue
            if op[0] == "put":
                store.put_files(op[1], op[2])
            elif op[0] == "get":
                for blob, st in store.get_files(op[1], op[2]):
                    _observe(blob, st)
            else:
                store.delete_file(op[1], op[2])
        return obs

    assert mode == "scheduler", mode
    sched = store.scheduler()
    gets: list = []

    def _flush() -> None:
        for req in sched.flush():
            if req.error is not None:
                raise req.error
        while gets:
            fut = gets.pop(0)
            for blob, st in fut.result():
                _observe(blob, st)

    since = 0
    for op in ops:
        if op[0] in ("add_shard", "drain_shard"):
            _flush()
            since = 0
            if lifecycle:
                _apply_lifecycle(store, op)
            continue
        if op[0] == "put":
            sched.submit_put(op[1], op[2])
        elif op[0] == "get":
            gets.append(sched.submit_get(op[1], op[2]))
        else:
            sched.submit_delete(op[1], [op[2]])
        since += 1
        if since >= flush_every:
            _flush()
            since = 0
    _flush()
    return obs


def artifacts(store: SEARSStore) -> dict:
    """Shard-topology-independent snapshot of everything observable."""
    nodes = {}
    for cl in store.clusters:
        for node in cl.nodes:
            h = hashlib.sha1()
            for cid, pidx in sorted(node._pieces):
                h.update(cid)
                h.update(pidx.to_bytes(4, "big"))
                h.update(hashlib.sha1(node._pieces[(cid, pidx)]).digest())
            nodes[(cl.cluster_id, node.node_id)] = h.hexdigest()
    records = sorted((cid, cl, info.refcount, info.length)
                     for cid, cl, info in store.index.records())
    listings = {user: sorted(sw.table)
                for user, sw in sorted(store.switching.items())}
    return {"nodes": nodes, "records": records, "listings": listings,
            "stats": store.stats()}


def assert_identical(base: tuple[list, dict],
                     subject: tuple[list, dict]) -> None:
    """Compare (observations, artifacts) pairs piecewise for locality."""
    base_obs, base_art = base
    subj_obs, subj_art = subject
    assert subj_obs == base_obs, "per-get observations diverged"
    for key in ("nodes", "records", "listings"):
        assert subj_art[key] == base_art[key], f"{key} diverged"
    assert subj_art["stats"] == base_art["stats"], "StoreStats diverged"


def assert_shard_balance(store: SEARSStore) -> None:
    """Every record/table/binding on its bucket owner; refcounts conserve
    per shard (drives the sanitizer's shard-ledger check ad hoc)."""
    Sanitizer(store).check_ledger()
    for sid in store.shard_map.live_ids():
        shard = store.shard_map.shards[sid]
        for cid in shard.index._chunks:
            assert store.shard_map.shard_of_chunk(cid) is shard
        for user in shard.tables:
            assert store.shard_map.shard_of_user(user) is shard


def run_differential(cfg: ShardTraceConfig, *, shards: int,
                     engine: str = "numpy", mode: str = "direct",
                     flush_every: int = 4) -> tuple[dict, dict]:
    """The reusable proof: same trace, 1 shard vs N shards (with any
    lifecycle ops applied only on the sharded side), byte-identical."""
    ops = multi_shard_trace(cfg)
    base = build_store(engine=engine, shards=1)
    base_obs = replay(base, ops, mode=mode, flush_every=flush_every,
                      lifecycle=False)
    subj = build_store(engine=engine, shards=shards)
    subj_obs = replay(subj, ops, mode=mode, flush_every=flush_every)
    assert_identical((base_obs, artifacts(base)),
                     (subj_obs, artifacts(subj)))
    assert_shard_balance(subj)
    return artifacts(base), artifacts(subj)


def run_cache_differential(cfg: ShardTraceConfig, *, shards: int = 1,
                           engine: str = "numpy", mode: str = "direct",
                           flush_every: int = 4,
                           write_back: bool = True,
                           capacity_bytes: int = 64 << 20
                           ) -> tuple[dict, dict]:
    """Cache-on vs cache-off byte identity on an *identical* topology.

    Both sides replay the same trace (lifecycle ops included on both —
    the cache must survive shard add/drain, not sidestep it); the
    subject additionally runs a block cache, write-back by default, and
    is flushed after the trace so every dirty chunk lands.  Timing
    stats legitimately diverge (hits skip the retrieval model's rng
    draws), so the per-get log compares blob digests only, and
    ``StoreStats.cache`` is normalized out; everything else — returned
    bytes, node piece digests, index records, listings, pool shape —
    must match byte-for-byte.
    """
    ops = multi_shard_trace(cfg)
    base = build_store(engine=engine, shards=shards)
    base_obs = replay(base, ops, mode=mode, flush_every=flush_every,
                      with_stats=False)
    subj = build_store(engine=engine, shards=shards,
                       cache=CacheConfig(capacity_bytes=capacity_bytes,
                                         write_back=write_back))
    subj_obs = replay(subj, ops, mode=mode, flush_every=flush_every,
                      with_stats=False)
    subj.flush()
    base_art, subj_art = artifacts(base), artifacts(subj)
    for art in (base_art, subj_art):
        art["stats"] = dataclasses.replace(art["stats"], cache=None)
    assert_identical((base_obs, base_art), (subj_obs, subj_art))
    assert_shard_balance(subj)  # includes the sanitizer's cache ledger
    assert subj.cache.dirty_count == 0, "flush left dirty chunks"
    return base_art, subj_art
