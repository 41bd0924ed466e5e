"""The real-time cell on the fused engine, small and on the CPU: a sound
run is correct, parity zeroed inside the fused hash+encode launch is
not, a traced run reads the share of the speculative encode that dedup
dropped, and the fused work counts read the distinct jobs of a window.
The cell is added as new files in a throwaway copy of ``bench/``."""

from __future__ import annotations

import json
import math
import sys
import time
from collections import namedtuple
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH / "tests")]

import tiny  # noqa: E402

from bench import harness  # noqa: E402
from bench.harness import load_module  # noqa: E402

SEED = 2**32 + 2**30 + 11
CELL = "tiny.upload_fused"
WORK = {name: load_module(BENCH / "work" / f"{name}.py")
        for name in ("fused_sha1", "fused_gf_encode")}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, benchmark = tiny.make(tmp_path_factory.mktemp("throwaway"))
    config = {**tiny.CONFIGS["tiny-rt"], "engine": "fused"}
    traffic = {k: v for k, v in tiny.TRAFFIC["tiny-upload"].items()
               if not k.startswith("warm_engine")}
    (root / "configs" / "tiny-rt-fused.json").write_text(json.dumps(config))
    (root / "traffic" / "tiny-upload-fused.json").write_text(
        json.dumps(traffic))
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"config": "tiny-rt-fused", "traffic": "tiny-upload-fused",
         "chips": 1}))
    for group in ("end_to_end", "per_layer"):
        for m in benchmark[group]:
            if "rt.upload_fused" in m.get("workloads", []):
                m["workloads"].append(CELL)
    peaks = json.loads((root / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (root / "peaks.json").write_text(json.dumps(peaks))
    return root, benchmark


def _run(copy, traced=False, fault=None):
    root, benchmark = copy
    run = harness.Run(harness.load_cell(CELL, root), SEED, traced,
                      started=time.perf_counter(), require_tpu=False,
                      bench_dir=root, log=lambda s: None)
    assert run.store.engine.name == "fused"
    if fault is not None:
        fault(run.store)
    return run.result(run.window(1.0), benchmark)


def _parity_zeroed(store):
    """Parity pieces of the fused launch written as zeros."""
    engine = store.engine
    fn = engine.hash_encode_blobs_multi

    def call(jobs):
        ids, pieces = fn(jobs)
        return ids, [ps[:code.k] + [bytes(len(p)) for p in ps[code.k:]]
                     for (code, _), ps in zip(jobs, pieces)]
    engine.hash_encode_blobs_multi = call


def test_sound_fused_run_is_correct(copy):
    out = _run(copy)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in harness.cell_metrics(copy[1], CELL, False)}
    assert set(out["metrics"]) == e2e


def test_fused_parity_zeroed_is_not_correct(copy):
    out = _run(copy, fault=_parity_zeroed)
    assert not out["correct"]
    assert any(c["value"] for c in out["checks"].values()
               if c["limit"] == 0)


def test_traced_fused_run_reads_the_dropped_share(copy):
    out = _run(copy, traced=True)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in harness.cell_metrics(copy[1], CELL, True)
            if m["source"] == "program_counter"}
    assert "spec_dropped_share.put" in want
    for name in want:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert out["metrics"]["spec_dropped_share.put"]["value"] <= 100


# ------------------------------------------------------- work counts ----
Code = namedtuple("Code", "n k")  # hashable, as the program's RSCode


@pytest.mark.parametrize("name", sorted(WORK))
def test_fused_work_reads_nothing_from_an_empty_window(name):
    assert WORK[name].calls("hash_encode_blobs_multi", ([],), {}) == []
    assert WORK[name].calls("hash_encode_blobs_multi", (),
                            {"jobs": []}) == []


def test_fused_sha1_work_counts_unpadded_messages_once():
    rt = Code(10, 5)
    jobs = [(rt, b"a" * 1000), (rt, b"b"), (rt, b"a" * 1000)]
    (call,) = WORK["fused_sha1"].calls("hash_encode_blobs_multi", (jobs,),
                                       {})
    # the duplicate shares a lane; padding to the bucket's cap is no work
    assert WORK["fused_sha1"].work(call) == (0.0, 1001 + 40)
    # the same bytes under another code are another job
    (call,) = WORK["fused_sha1"].calls(
        "hash_encode_blobs_multi", (jobs + [(Code(14, 10), b"b")],), {})
    assert WORK["fused_sha1"].work(call) == (0.0, 1002 + 60)


def test_fused_gf_encode_work_counts_parity_rows_once():
    rt, bk = Code(10, 5), Code(14, 10)
    (call,) = WORK["fused_gf_encode"].calls(
        "hash_encode_blobs_multi", ([(rt, b"x"), (rt, b"x")],), {})
    ops, nbytes = WORK["fused_gf_encode"].work(call)
    # one 1-byte chunk: 5 rows in, 5 parity rows out, counted once
    assert ops == 2 * 64 * 5 * 5 * 1 and nbytes == 10
    (call,) = WORK["fused_gf_encode"].calls(
        "hash_encode_blobs_multi", ([(bk, b"x" * 8195), (rt, b"x")],), {})
    ops, nbytes = WORK["fused_gf_encode"].work(call)
    assert ops == 2 * 64 * (4 * 10 * 820 + 5 * 5 * 1)
    assert nbytes == 14 * 820 + 10
