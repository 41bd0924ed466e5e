"""Traced runs of small cells on the CPU report the program's own step
and transfer metrics (``program_counter``, read from ``SchedulerStats``)
as finite numbers.  The cells and a CPU entry in the peaks are added to
a throwaway copy of ``bench/``."""

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

from bench import harness  # noqa: E402

SEED = 2**32 + 2**31 + 5


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, benchmark = tiny.make(tmp_path_factory.mktemp("throwaway"))
    peaks = json.loads((root / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (root / "peaks.json").write_text(json.dumps(peaks))
    return root, benchmark


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_traced_run_reports_every_program_counter(copy, cell):
    root, benchmark = copy
    out = harness.run_cell(
        harness.load_cell(cell, root), SEED, 1.0, True,
        benchmark=benchmark, started=time.perf_counter(),
        require_tpu=False, bench_dir=root, log=lambda s: None)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in harness.cell_metrics(benchmark, cell, True)
            if m["source"] == "program_counter"}
    assert len(want) >= 7
    for name in want:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    h2d = next(v["value"] for name, v in out["metrics"].items()
               if name.startswith("h2d_bytes_per_byte."))
    assert h2d > 1  # padding of the gear stream, SHA-1 blocks, GF tiles
