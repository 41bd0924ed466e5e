"""The benchmark's parts on their own: traffic, work counts, trace
reduction and the files that name them.  No chip and no JAX needed."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT)]

from bench import reference, trace, traffic  # noqa: E402
from bench.harness import cell_metrics, load_cell, load_module  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
RT = json.loads((BENCH / "configs" / "sears-rt-3mb.json").read_text())
BK = json.loads((BENCH / "configs" / "sears-archival-backup.json")
                .read_text())
WORK = {p.stem: load_module(p) for p in (BENCH / "work").glob("*.py")}


# ----------------------------------------------------------- traffic ----
def _small(config, **data):
    return {**config, "data": {**config["data"], **data}}


def test_mixed_files_repeat_per_seed():
    cfg = _small(RT, file_bytes=40_000)
    a = traffic.make_source(2**33 + 5, cfg, {"clients": 3})
    b = traffic.make_source(2**33 + 5, cfg, {"clients": 3})
    c = traffic.make_source(2**33 + 6, cfg, {"clients": 3})
    assert a.round(4) == b.round(4)
    assert a.content(1, 4) == b.content(1, 4)
    assert a.content(1, 4) != c.content(1, 4)
    assert a.content(1, 4) != a.content(2, 4)
    assert len(a.content(0, 0)) == 40_000


def test_mixed_files_share_pool_blocks():
    cfg = _small(RT, file_bytes=64 * 8192)
    src = traffic.make_source(11, cfg, {"clients": 2})
    pool = {bytes(b) for b in src.pool}
    blocks = [src.content(c, 0)[i:i + 8192] for c in range(2)
              for i in range(0, 64 * 8192, 8192)]
    shared = sum(b in pool for b in blocks) / len(blocks)
    assert 0.2 < shared < 0.5  # shared_fraction 0.35


def test_backup_images_repeat_and_churn():
    cfg = _small(BK, image_bytes=1 << 20)
    a = traffic.make_source(2**40 + 1, cfg, {"clients": 10})
    b = traffic.make_source(2**40 + 1, cfg, {"clients": 10})
    night2 = a.content(3, 2)
    night1 = a.content(3, 1)  # going back replays from night 0
    assert b.content(3, 2) == night2
    assert a.content(3, 2) == night2
    diff = np.frombuffer(night1, np.uint8) != np.frombuffer(night2, np.uint8)
    assert 0.01 < diff.mean() < 0.04  # 3% churn in 4 KiB spots
    assert len(a.round(0)) == 10


def test_arrivals_fixed_by_the_traffic_not_the_seed():
    a = traffic.arrivals(8.0, 30.0, 2**35 + 1)
    b = traffic.arrivals(8.0, 30.0, 2**35 + 1)
    c = traffic.arrivals(8.0, 30.0, 2**35 + 2)
    assert len(a) == len(c) == 240 and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a[0] == 0.0 and c[0] == 0.0 and np.all(np.diff(a) > 0)
    assert a[-1] < 30.0 and c[-1] < 30.0
    # the same gaps in another order: all but the one left at the end
    ga, gc = (set(np.round(np.diff(t), 9)) for t in (a, c))
    assert len(ga ^ gc) <= 2


def test_percentile():
    assert traffic.percentile([1, 2, 3, 4, 5], 50) == 3
    assert traffic.percentile([1, 2], 95) == pytest.approx(1.95)


# ------------------------------------------------------- work counts ----
def _code(n, k):
    return SimpleNamespace(n=n, k=k)


def test_gear_work_counts_stream_not_bucket():
    (call,) = WORK["gear"].calls(
        "chunk_blobs_multi_begin",
        ([("c", np.zeros(100, np.uint8)), ("c", np.zeros(60, np.uint8))],),
        {})
    assert call == {"stream_bytes": 160}
    # the kernel runs a padded 8192-byte tile; only 160 bytes are needed
    assert WORK["gear"].work(call) == (0.0, 160 + 20)


def test_sha1_work_counts_unpadded_messages():
    (call,) = WORK["sha1"].calls("hash_chunks", ([b"a" * 1000, b"b"],), {})
    assert WORK["sha1"].work(call) == (0.0, 1001 + 40)


def test_gf_encode_work_counts_parity_rows_only():
    # a 1-byte chunk under (10, 5): rows of 1 byte, padded to 512 by the
    # kernel; the required work is 5 rows in, 5 parity rows out
    (call,) = WORK["gf_encode"].calls(
        "encode_blobs_multi", ([(_code(10, 5), b"x")],), {})
    ops, nbytes = WORK["gf_encode"].work(call)
    assert ops == 2 * 64 * 5 * 5 * 1 and nbytes == 10
    (call,) = WORK["gf_encode"].calls(
        "encode_blobs_multi", ([(_code(14, 10), b"x" * 8195)],), {})
    ops, nbytes = WORK["gf_encode"].work(call)
    assert ops == 2 * 64 * 4 * 10 * 820 and nbytes == 14 * 820


def test_gf_decode_work_counts_missing_rows_only():
    code = _code(10, 5)
    healthy = (code, {j: b"" for j in range(5)}, 4096)
    one_down = (code, {j: b"" for j in range(1, 6)}, 4096)
    assert WORK["gf_decode"].calls("decode_blobs_multi",
                                   ([healthy],), {}) == []
    (call,) = WORK["gf_decode"].calls("decode_blobs_multi",
                                      ([healthy, one_down],), {})
    ops, nbytes = WORK["gf_decode"].work(call)
    L = 4096 // 5 + 1
    assert ops == 2 * 64 * 1 * 5 * L and nbytes == 6 * L


# ----------------------------------------------------- trace reduce -----
def _brute_busy(events, w0, w1, step=1.0):
    t = np.arange(w0, w1, step) + step / 2
    busy = np.zeros_like(t, bool)
    for _, _, _, s, d in events["device"]:
        busy |= (t >= s) & (t < s + d)
    return busy.sum() * step * 1e-9


def test_reduce_hand_made():
    ev = {"host": [["bench.window", 0.0, 100.0],
                   ["bench.flush", 5.0, 60.0],
                   ["bench.engine.hash_chunks", 20.0, 10.0],
                   ["bench.generate", 80.0, 10.0]],
          "device": [["/device:TPU:0", "_sha1_padded.1", "jit__sha1_padded",
                      20.0, 5.0],
                     ["/device:TPU:0", "_sha1_padded.1", "jit__sha1_padded",
                      22.0, 6.0],
                     ["/device:TPU:0", "_gf_matmul_padded.1",
                      "jit__gf_matmul_padded", 50.0, 10.0],
                     ["/device:TPU:0", "copy.2", "jit__gf_matmul_padded",
                      95.0, 10.0]]}
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(90e-9)
    assert r["busy_s"] == pytest.approx((8 + 10 + 5) * 1e-9)
    assert trace.kernel_seconds(r, ["_sha1_padded"]) == pytest.approx(11e-9)
    assert trace.kernel_seconds(r, ["_gf_matmul_padded"]) == pytest.approx(
        10e-9)  # the module's copy is not the kernel
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 0-20 and 28-50 in the flush; 60-80 and 90-95 after it; 80-90 is
    # the load generator's, out of the window
    assert gaps["bench.flush"] == pytest.approx(42e-9)
    assert gaps["host"] == pytest.approx(25e-9)
    assert "bench.generate" not in gaps
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_recorded_trace():
    """A slice of a traced rt.upload window on a TPU v5 lite."""
    ev = json.loads((BENCH / "tests" / "fixtures" / "trace_small.json")
                    .read_text())
    r = trace.reduce(ev)
    (w,) = [h for h in ev["host"] if h[0] == "bench.window"]
    assert r["window_s"] == pytest.approx(w[2] * 1e-9)
    assert r["busy_s"] == pytest.approx(
        _brute_busy(ev, w[1], w[1] + w[2], step=100.0), rel=0.01)
    assert 0 < r["busy_s"] < r["window_s"]
    total = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert total == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    mods = r["module_s"]
    for kernel in ("gear", "sha1", "gf_encode"):
        assert trace.kernel_seconds(r, WORK[kernel].TRACE_OPS) > 0
    assert sum(mods.values()) >= r["busy_s"] * 0.999


# ------------------------------------------------------ reference -------
def test_reference_rs_is_systematic_mds():
    n, k = 10, 5
    parity = reference.parity_matrix(n, k)
    chunk = bytes(range(256)) * 9
    pieces = reference.encode(chunk, n, k, parity)
    L = -(-len(chunk) // k)
    assert all(len(p) == L for p in pieces)
    assert b"".join(pieces[:k])[:len(chunk)] == chunk
    # any parity row of a one-hot data column is its Cauchy coefficient
    one = bytes([1]) + bytes(k * 1 - 1)
    ps = reference.encode(one, n, k, parity)
    assert [p[0] for p in ps[k:]] == [row[0] for row in parity]


def test_reference_chunking_bounds():
    data = np.random.default_rng(3).integers(0, 256, 600_000,
                                             dtype=np.uint8).tobytes()
    lengths = reference.chunk_lengths(data, 1024, 4096, 8192)
    assert sum(lengths) == len(data)
    assert all(1024 <= ln <= 8192 for ln in lengths[:-1])
    assert 2000 < np.mean(lengths) < 7000


def test_reference_agrees_with_the_program_on_the_cpu():
    """The reference is written apart from the program; on the CPU the
    two agree on chunk boundaries and on every piece."""
    from repro.core.chunking import Chunker
    from repro.core.rs_code import RSCode
    data = np.random.default_rng(4).integers(0, 256, 300_001,
                                             dtype=np.uint8).tobytes()
    for cmin, cavg, cmax in ((1024, 4096, 8192), (2048, 8192, 16384)):
        want = [ln for _, ln in Chunker(cmin, cavg, cmax).chunk_spans(data)]
        assert reference.chunk_lengths(data, cmin, cavg, cmax) == want
    for n, k in ((10, 5), (14, 10)):
        parity = reference.parity_matrix(n, k)
        for size in (1, 4097, 8192):
            chunk = data[:size]
            assert reference.encode(chunk, n, k, parity) == \
                RSCode(n, k).encode_bytes(chunk)


# ------------------------------------------------- benchmark files ------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_named_file_exists():
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"])
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    for w in BENCHMARK["workloads"]:
        cell = load_cell(w["name"])
        assert (cell.config_name, cell.traffic_name, cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        assert len(w["why"]) <= 200
        assert cell_metrics(BENCHMARK, w["name"], False)
        assert cell_metrics(BENCHMARK, w["name"], True)
    for c in BENCHMARK["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    for mod in WORK.values():
        assert mod.ENGINE_CALLS and mod.TRACE_OPS


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: set(m.get("workloads", [])) for m in
           BENCHMARK["end_to_end"]}
    layers = {}
    for m in BENCHMARK["per_layer"]:
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert layers.setdefault(m["name"].split(".")[0], m["layer"]) \
            == m["layer"]
