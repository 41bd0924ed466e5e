"""The archival nightly-backup cell on the fused engine, small and on the
CPU: a sound run is correct, parity zeroed inside the fused hash+encode
launch is not, and a traced run reads most of the speculative encode as
dropped, with its host cost per MiB; that cost reads nothing on a
staged engine.  The cell is added as new files in a throwaway copy of
``bench/``."""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH / "tests")]

import tiny  # noqa: E402
from test_bench_fused import _parity_zeroed  # noqa: E402

from bench import harness  # noqa: E402

SEED = 2**32 + 2**30 + 23
CELL = "tiny.nightly_fused"
STAGED = "tiny.nightly"
METRIC = "spec_host_ms_per_MiB.put"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, benchmark = tiny.make(tmp_path_factory.mktemp("throwaway"))
    config = {**tiny.CONFIGS["tiny-backup"], "engine": "fused"}
    traffic = {k: v for k, v in tiny.TRAFFIC["tiny-nightly"].items()
               if not k.startswith("warm_engine")}
    (root / "configs" / "tiny-backup-fused.json").write_text(
        json.dumps(config))
    (root / "traffic" / "tiny-nightly-fused.json").write_text(
        json.dumps(traffic))
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"config": "tiny-backup-fused", "traffic": "tiny-nightly-fused",
         "chips": 1}))
    for group in ("end_to_end", "per_layer"):
        for m in benchmark[group]:
            if "backup.nightly_fused" in m.get("workloads", []):
                m["workloads"].append(CELL)
            if m["name"] == METRIC:
                m["workloads"].append(STAGED)
    peaks = json.loads((root / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (root / "peaks.json").write_text(json.dumps(peaks))
    return root, benchmark


def _run(copy, cell=CELL, traced=False, fault=None):
    root, benchmark = copy
    run = harness.Run(harness.load_cell(cell, root), SEED, traced,
                      started=time.perf_counter(), require_tpu=False,
                      bench_dir=root, log=lambda s: None)
    if fault is not None:
        fault(run.store)
    return run.result(run.window(1.0), benchmark)


def test_sound_fused_backup_run_is_correct(copy):
    out = _run(copy)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in harness.cell_metrics(copy[1], CELL, False)}
    assert set(out["metrics"]) == e2e


def test_fused_backup_parity_zeroed_is_not_correct(copy):
    out = _run(copy, fault=_parity_zeroed)
    assert not out["correct"]
    assert any(c["value"] for c in out["checks"].values()
               if c["limit"] == 0)


def test_traced_fused_backup_drops_most_of_the_encode(copy):
    out = _run(copy, traced=True)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in harness.cell_metrics(copy[1], CELL, True)
            if m["source"] == "program_counter"}
    assert {METRIC, "spec_dropped_share.put"} <= want
    for name in want:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert 50 < out["metrics"]["spec_dropped_share.put"]["value"] <= 100


def test_spec_host_cost_reads_nothing_on_a_staged_engine(copy):
    out = _run(copy, cell=STAGED, traced=True)
    assert out["correct"], out["checks"]
    assert "engine_host_ms_per_MiB.put" in out["metrics"]
    assert METRIC not in out["metrics"]
