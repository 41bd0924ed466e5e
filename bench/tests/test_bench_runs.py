"""Whole runs of small cells on the CPU, past the harness's look for a
chip: a sound run is correct, and every planted fault turns ``correct``
false.  The cells are added as new files in a throwaway copy of
``bench/``, the way a later change adds one."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH / "tests")]

import tiny  # noqa: E402

from bench import harness  # noqa: E402

SEED = 2**33 + 17


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("throwaway"))


def _run(copy, cell, fault=None, seed=SEED):
    root, benchmark = copy
    return harness.run_cell(
        harness.load_cell(cell, root), seed, 1.0, False,
        benchmark=benchmark, started=time.perf_counter(),
        require_tpu=False, fault=fault, bench_dir=root, log=lambda s: None)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(copy, cell):
    out = _run(copy, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    e2e = {m["name"] for m in harness.cell_metrics(copy[1], cell, False)}
    assert set(out["metrics"]) == e2e
    assert all(c["limit"] in (0, None) for c in out["checks"].values())


FAULTS = [("tiny.upload", "parity_zeroed"), ("tiny.nightly", "parity_zeroed"),
          ("tiny.get", "decode_skipped")] + [
    (cell, fault) for cell in ("tiny.upload", "tiny.get")
    for fault in ("state_unchanged", "half_batch", "answer_altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(copy, cell, fault):
    out = _run(copy, cell, fault)
    assert not out["correct"]
    assert any(c["value"] for n, c in out["checks"].items()
               if c["limit"] == 0)


def test_command_off_the_tpu_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "rt.upload", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_without_the_program_prints_no_result(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "rt.upload", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("cpu")
    assert json.loads((BENCH / "peaks.json").read_text())["source"]
