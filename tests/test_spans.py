"""Spans and transfer counters inside the put and get windows.

``kernels.launches`` times each ``sears.*`` step into ``SPANS`` and counts
host<->device bytes into ``TRANSFERS``; ``BatchScheduler.flush`` folds a
flush's deltas into ``SchedulerStats``.  On the CPU with the jitted
oracles (``impl="ref"``), the same dispatch sites as on the chip.
"""

import dataclasses
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import SEARSStore
from repro.core.classes import StorageClass
from repro.core.engine import KernelEngine
from repro.core.scheduler import SPAN_FIELDS
from repro.kernels import gear_cdc, ops
from repro.kernels.launches import (SPANS, TRANSFERS, delta_all,
                                    snapshot_all, span)

PUT_FIELDS = ("put_chunk_s", "put_slice_s", "put_hash_s", "put_plan_s",
              "put_encode_s", "put_write_s")
GET_FIELDS = ("get_plan_s", "get_read_s", "get_hint_s", "get_decode_s",
              "get_assemble_s")
ENGINE_FIELDS = ("engine_pack_s", "engine_dispatch_s", "engine_wait_s",
                 "engine_unpack_s")
BYTE_FIELDS = ("h2d_bytes", "d2h_bytes")
CLASS = StorageClass(name="realtime", n=10, k=5, chunk_min=1024,
                     chunk_avg=4096, chunk_max=8192, binding="ulb")
FILE_BYTES = 16 << 10


def _store(hash_batch=None):
    engine = KernelEngine(impl="ref", hash_batch=hash_batch)
    return SEARSStore(classes=[CLASS], num_clusters=3,
                      node_capacity=1 << 26, engine=engine, sanitize=False)


def _files(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(f"f{i}", rng.integers(0, 256, FILE_BYTES, np.uint8).tobytes())
            for i in range(n)]


def _put(sched, files):
    futures = [sched.submit_put("u", [f]) for f in files]
    sched.flush()
    for f in futures:
        f.result()


def _stats(sched):
    return dataclasses.replace(sched.stats)


def _delta(after, before, name):
    return getattr(after, name) - getattr(before, name)


def test_put_and_degraded_get_windows_fill_every_field():
    store = _store()
    sched = store.scheduler()
    files = _files(6)
    s0 = _stats(sched)
    _put(sched, files)
    s1 = _stats(sched)
    for name in PUT_FIELDS + ENGINE_FIELDS + BYTE_FIELDS:
        assert _delta(s1, s0, name) > 0, name
    steps = sum(_delta(s1, s0, f) for f in PUT_FIELDS)
    assert steps <= _delta(s1, s0, "flush_seconds")
    assert all(_delta(s1, s0, f) == 0 for f in GET_FIELDS)

    for c in store.clusters:
        c.kill_nodes([0])  # every chunk decodes through parity
    futures = [sched.submit_get("u", [name]) for name, _ in files[:3]]
    sched.flush()
    assert [f.result()[0][0] for f in futures] == [d for _, d in files[:3]]
    s2 = _stats(sched)
    for name in GET_FIELDS + ENGINE_FIELDS + BYTE_FIELDS:
        assert _delta(s2, s1, name) > 0, name
    steps = sum(_delta(s2, s1, f) for f in GET_FIELDS)
    assert steps <= _delta(s2, s1, "flush_seconds")
    assert all(_delta(s2, s1, f) == 0 for f in PUT_FIELDS)


def test_every_step_field_is_a_plain_number_named_by_a_span():
    stats = _store().scheduler().stats
    fields = {f.name for f in dataclasses.fields(stats)}
    assert set(SPAN_FIELDS.values()) <= fields
    assert set(PUT_FIELDS + GET_FIELDS + ENGINE_FIELDS) == set(
        SPAN_FIELDS.values())
    for name in fields:
        assert isinstance(getattr(stats, name), (int, float)), name


def _record(monkeypatch, name, sink, out_bytes):
    """Wrap a jitted oracle of ``ops``; sink gets (args, out nbytes)."""
    fn = getattr(ops, name)

    def call(*args):
        out = fn(*args)
        sink.append((name, [np.shape(a) for a in args], out_bytes(out)))
        return out
    monkeypatch.setattr(ops, name, call)


def test_h2d_bytes_equal_the_launched_shapes(monkeypatch):
    sched = _store(hash_batch=16).scheduler()
    files = _files(10, seed=1)
    seen = []
    _record(monkeypatch, "_gear_fire_ref", seen, lambda out: None)
    _record(monkeypatch, "_sha1_ref_loop", seen,
            lambda out: int(np.prod(out.shape)) * 4)
    _record(monkeypatch, "_gf_ref_jit", seen,
            lambda out: int(np.prod(out.shape)))
    before = TRANSFERS.snapshot()
    _put(sched, files)
    moved = TRANSFERS.delta(before)

    stream = sum(len(d) for _, d in files)
    gear = [shapes for name, shapes, _ in seen if name == "_gear_fire_ref"]
    sha1 = [shapes for name, shapes, _ in seen if name == "_sha1_ref_loop"]
    gf = [shapes for name, shapes, _ in seen if name == "_gf_ref_jit"]
    assert gear == [[(gear_cdc.bucket_len(stream),), ()]]
    assert len(sha1) > 1 and gf  # several hash batches, some GF buckets
    h2d = gear_cdc.bucket_len(stream) + 4  # the stream and its mask
    for blocks, counts in sha1:
        assert blocks[1:] == (blocks[1], 16) and counts == blocks[:1]
        h2d += 4 * int(np.prod(blocks)) + 4 * counts[0]
    for matrix, data in gf:
        assert data[1] == CLASS.k and data[2] % 512 == 0
        h2d += int(np.prod(data))  # the coding matrix stays on the device
    assert moved.h2d_bytes == h2d
    # back: one fire flag per stream byte, 5 digest words per hash lane,
    # n rows per GF lane
    d2h = stream + sum(b for name, _, b in seen if b is not None)
    assert moved.d2h_bytes == d2h


def _window_counts(n_files):
    sched = _store(hash_batch=16).scheduler()
    before = snapshot_all()
    _put(sched, _files(n_files, seed=2))
    d = delta_all(before)
    return d["spans"].count, d["launches"]


def test_span_count_does_not_grow_with_the_chunk_count():
    few, few_launches = _window_counts(10)
    many, many_launches = _window_counts(100)
    assert many_launches.total > few_launches.total  # more buckets
    for name in set(few) | set(many):
        if name.startswith("sears.engine."):
            # one span per kernel bucket (launch), plus a fixed few
            assert (many[name] - many_launches.total
                    == few[name] - few_launches.total), name
        else:
            assert many[name] == few[name], name
    assert few["sears.flush"] == 1 and few["sears.put.plan"] == 1
    assert few["sears.engine.dispatch"] == few_launches.total


def test_numpy_engine_store_flushes_without_jax():
    code = textwrap.dedent("""
        import sys
        from repro.core import SEARSStore
        from repro.kernels.launches import SPANS
        store = SEARSStore(engine="numpy", num_clusters=2)
        sched = store.scheduler()
        sched.submit_put("u", [("a", bytes(range(256)) * 64)])
        sched.flush()
        got = sched.submit_get("u", ["a"])
        sched.flush()
        assert got.result()[0][0] == bytes(range(256)) * 64
        assert sched.stats.put_plan_s > 0 and sched.stats.get_read_s > 0
        assert SPANS.count["sears.flush"] == 2
        assert "jax" not in sys.modules, "a numpy store imported jax"
    """)
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr


def test_span_adds_seconds_and_entries():
    before = SPANS.snapshot()
    for _ in range(3):
        with span("sears.test.step"):
            pass
    d = SPANS.delta(before)
    assert d.count == {"sears.test.step": 3}
    assert d.seconds["sears.test.step"] >= 0
    with pytest.raises(KeyError):
        with span("sears.test.raises"):
            raise KeyError("x")  # the step is timed, the error passes
    assert SPANS.delta(before).count["sears.test.raises"] == 1


# what each step runs inside, as README's Observability section has it
PARENTS = {"sears.put.": ("sears.flush",), "sears.get.": ("sears.flush",),
           "sears.engine.": ("sears.put.chunk", "sears.put.hash",
                             "sears.put.encode", "sears.get.decode")}


def test_profiler_trace_holds_the_spans_nested(tmp_path):
    import jax
    from jax.profiler import ProfileData
    store = _store()
    sched = store.scheduler()
    files = _files(4, seed=3)
    _put(sched, files)  # compile outside the trace
    for c in store.clusters:
        c.kill_nodes([0])
    sched.submit_get("u", [files[0][0]])
    sched.flush()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _put(sched, _files(4, seed=4))
        sched.submit_get("u", [files[1][0]])
        sched.flush()
    finally:
        jax.profiler.stop_trace()
    events = []
    for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for line in plane.lines for e in line.events
                           if e.name.startswith("sears.")]
    names = {name for name, _, _ in events}
    assert {"sears.flush", "sears.engine.dispatch",
            "sears.engine.wait"} <= names
    assert {f"sears.put.{s}" for s in
            ("chunk", "slice", "hash", "plan", "encode", "write")} <= names
    assert {f"sears.get.{s}" for s in
            ("plan", "read", "hint", "decode", "assemble")} <= names
    for name, start, end in events:
        parents = next((p for prefix, p in PARENTS.items()
                        if name.startswith(prefix)), None)
        if parents is None:
            continue
        assert any(p == pn and ps <= start and end <= pe
                   for p in parents for pn, ps, pe in events), name
