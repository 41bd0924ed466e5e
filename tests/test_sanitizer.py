"""Runtime sanitizer (``SEARSStore(..., sanitize=True)``): zero findings
on correct flows across all engines, injected violations caught."""

import os

import numpy as np
import pytest

from repro.core.sanitizer import Sanitizer, SanitizerError
from repro.core.store import SEARSStore


def _data(n, seed=0):
    return np.random.RandomState(seed).randint(  # noqa: NPY002
        0, 256, size=n, dtype=np.uint8).tobytes()


def _store(engine="numpy", **kw):
    kw.setdefault("num_clusters", 4)
    kw.setdefault("node_capacity", 64 << 20)
    kw.setdefault("sanitize", True)
    return SEARSStore(n=10, k=5, binding="ulb", engine=engine, **kw)


def _files(n_files=4, base=9_000, seed=3):
    return [(f"f{i}", _data(base + 700 * i, seed=seed + i))
            for i in range(n_files)]


ENGINES = ["numpy", "kernel", "fused"]


# ------------------------------------------------- clean flows, all engines --

def _lifecycle(s):
    """put/get/overwrite/delete/degraded-get/repair; returns all bytes read."""
    files = _files()
    s.put_files("u", files)
    s.put_file("u", files[0][0], _data(11_000, seed=99))  # overwrite
    s.delete_file("u", files[1][0])
    reads = [s.get_file("u", fn)[0] for fn, _ in files[2:]]
    s.clusters[0].kill_nodes([0, 1])
    reads.append(s.get_file("u", files[2][0])[0])  # degraded decode
    s.clusters[0].revive_nodes([0, 1])
    s.repair_all()
    reads.append(s.get_file("u", files[3][0])[0])
    return reads


@pytest.mark.parametrize("engine", ENGINES)
def test_sanitized_lifecycle_is_clean_and_differential(engine):
    """The full lifecycle under the sanitizer matches an unsanitized
    store byte-for-byte, with zero findings."""
    plain = _store(engine=engine, sanitize=False)
    plain_reads = _lifecycle(plain)
    san = _store(engine=engine)
    san_reads = _lifecycle(san)
    assert san_reads == plain_reads

    assert san._sanitizer is not None and san._sanitizer.checks > 0
    assert plain._sanitizer is None


def test_interleaved_sanitized_stores_do_not_cross_contaminate():
    """Two sanitized kernel stores alternating traffic: the launch
    model attributes each store's dispatches to it alone, so neither
    sees the other's launches as its own (LAUNCHES is process-global)."""
    a = _store(engine="kernel")
    b = _store(engine="kernel")
    files = _files(n_files=4)
    for i, (fn, blob) in enumerate(files):
        s = a if i % 2 == 0 else b
        s.put_file("u", fn, blob)       # a and b alternate put windows
    for i, (fn, blob) in enumerate(files):
        s = a if i % 2 == 0 else b
        out, _ = s.get_file("u", fn)
        assert out == blob
    assert a._sanitizer.checks > 0 and b._sanitizer.checks > 0


@pytest.mark.parametrize("engine", ENGINES)
def test_sanitized_pipelined_windows_match_sequential(engine):
    files = _files(n_files=6)
    wins = [[("u", files[:3])], [("u", files[3:])]]

    seq = _store(engine=engine, sanitize=False)
    for _, fs in wins[0] + wins[1]:
        seq.put_files("u", fs)

    pipe = _store(engine=engine)
    sched = pipe.scheduler()
    for i, (_, fs) in enumerate(wins[0] + wins[1]):
        if i:  # a get between the puts keeps them two put windows
            sched.submit_get("u", [files[0][0]])
        sched.submit_put("u", fs)
    assert all(r.ok for r in sched.flush())
    assert sched.stats.n_pipelined_windows == 1

    for fn, blob in files:
        out, _ = pipe.get_file("u", fn)
        assert out == blob
    assert seq.stats() == pipe.stats()
    assert pipe._sanitizer.checks > 0


def test_sanitized_scheduler_pipeline_flush():
    s = _store()
    sched = s.scheduler()
    reqs = [sched.submit_put(u, _files(n_files=2, seed=i))
            for i, u in enumerate(("alice", "bob", "carol"))]
    sched.flush()
    assert all(r.ok for r in reqs)
    gets = [sched.submit_get(u, [fn for fn, _ in _files(n_files=2, seed=i)])
            for i, u in enumerate(("alice", "bob", "carol"))]
    sched.flush()
    assert all(r.ok for r in gets)
    assert s._sanitizer.checks > 0


# ----------------------------------------------------------- injected bugs --

def test_begin_phase_mutation_is_caught():
    s = _store()
    files = _files(n_files=2)
    real = s.engine.chunk_blobs_multi_begin

    def evil_begin(jobs):
        s._nfiles["u"] = s._nfiles.get("u", 0) + 100  # control-plane write
        return real(jobs)

    s.engine.chunk_blobs_multi_begin = evil_begin
    with pytest.raises(SanitizerError, match="begin-phase"):
        s.put_files("u", files)


def test_per_chunk_dispatch_breaks_launch_model():
    """An engine hashing chunk-by-chunk (instead of per-batch) must blow
    the expected-launch budget."""
    from repro.kernels.launches import LAUNCHES

    s = _store()
    real = s.engine.hash_chunks

    def leaky_hash(chunks):
        LAUNCHES.sha1 += len(chunks)  # one fake dispatch per chunk
        return real(chunks)

    s.engine.hash_chunks = leaky_hash
    with pytest.raises(SanitizerError, match="launch model"):
        s.put_files("u", _files())


def test_refcount_forgery_breaks_ledger():
    s = _store()
    s.put_files("u", _files(n_files=2))
    cid, _cl, info = next(s.index.records())

    def forge_and_flush():
        info.refcount += 1
        s.put_file("u", "trigger", _data(8_000, seed=42))

    with pytest.raises(SanitizerError, match="ledger"):
        forge_and_flush()


def _lose_home_with_donor(s):
    """Duplicate one user's files onto a second ULB user, then declare
    the first user's home cluster lost -- queues re-placement work with
    a healthy donor copy available."""
    files = _files(n_files=3)
    s.put_files("u", files)
    s.put_files("v", files)  # ULB: same bytes, different home cluster
    lost_id = s.binding._bound["u"]
    s.declare_cluster_lost(lost_id)
    return lost_id


def test_per_piece_dispatch_during_replacement_breaks_launch_model():
    """Cross-cluster re-placement shares the in-place recode budget
    (2 GF launches per job): an engine encoding each target piece with
    its own dispatch must trip the expected-launch model in the drain."""
    from repro.kernels.launches import LAUNCHES

    s = _store()
    _lose_home_with_donor(s)
    real = s.engine.recode_blobs_multi

    def leaky_recode(jobs):
        LAUNCHES.gf += s.n * len(jobs)  # one fake dispatch per piece
        return real(jobs)

    s.engine.recode_blobs_multi = leaky_recode
    with pytest.raises(SanitizerError, match="launch model"):
        s.repair.repair()


def test_refcount_forgery_after_replacement_breaks_ledger():
    """A half-committed move (target copy's refcount forged after the
    drain) must be caught by the ledger check at the next window."""
    s = _store()
    _lose_home_with_donor(s)
    report = s.repair.repair()
    assert report.replaced and report.balanced
    cid, _, new_id = report.replaced[0]
    s.index.get(cid, new_id).refcount += 1  # forge the moved copy
    with pytest.raises(SanitizerError, match="ledger"):
        s.put_file("u", "trigger", _data(8_000, seed=42))


def test_foreign_launch_traffic_is_ignored_and_resync_rebaselines():
    from repro.kernels.launches import LAUNCHES

    s = _store()
    s.put_files("u", _files(n_files=2))
    LAUNCHES.gf += 50  # someone else's traffic, outside our brackets
    s.put_file("u", "more", _data(9_500, seed=5))  # model unaffected
    s._sanitizer.resync()  # fresh ledger: zero seen, zero budget
    out, _ = s.get_file("u", "more")  # get re-budgets its own decode
    assert out == _data(9_500, seed=5)


# ------------------------------------------------------------- activation --

def test_env_var_opt_in(monkeypatch):
    monkeypatch.setenv("SEARS_SANITIZE", "1")
    s = SEARSStore(n=4, k=2, num_clusters=2)
    assert isinstance(s._sanitizer, Sanitizer)
    monkeypatch.setenv("SEARS_SANITIZE", "0")
    assert SEARSStore(n=4, k=2, num_clusters=2)._sanitizer is None
    monkeypatch.delenv("SEARS_SANITIZE")
    assert SEARSStore(n=4, k=2, num_clusters=2)._sanitizer is None


def test_explicit_flag_beats_env(monkeypatch):
    monkeypatch.setenv("SEARS_SANITIZE", "1")
    assert SEARSStore(n=4, k=2, num_clusters=2,
                      sanitize=False)._sanitizer is None
    monkeypatch.delenv("SEARS_SANITIZE")
    s = SEARSStore(n=4, k=2, num_clusters=2, sanitize=True)
    assert isinstance(s._sanitizer, Sanitizer)
