"""A throwaway copy of the benchmark's files with small cells, for tests.

``make(tmp)`` copies ``bench/`` (metrics, work counts, peaks, configs,
traffic, cells) into ``tmp`` and adds cells at a size the CPU runs in
seconds.  Nothing in ``bench/`` is edited: the new cells are new files,
as a later change would add them.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIGS = {
    "tiny-rt": {
        "storage_class": {"name": "realtime", "n": 10, "k": 5,
                          "chunk_min": 1024, "chunk_avg": 4096,
                          "chunk_max": 8192, "binding": "ulb",
                          "dedup": "pool", "priority": 0},
        "num_clusters": 3, "node_capacity": 1 << 26, "engine": "kernel",
        "shards": 1, "cache": False,
        "scheduler": {"flush_bytes": 96 << 10},
        "data": {"source": "mixed_files", "file_bytes": 32 << 10,
                 "block_bytes": 8192, "shared_fraction": 0.35,
                 "pool_blocks": 16}},
    "tiny-backup": {
        "storage_class": {"name": "archival", "n": 14, "k": 10,
                          "chunk_min": 2048, "chunk_avg": 8192,
                          "chunk_max": 16384, "binding": "clb",
                          "dedup": "pool", "priority": 2},
        "num_clusters": 3, "node_capacity": 1 << 26, "engine": "kernel",
        "shards": 1, "cache": False,
        "scheduler": {"flush_bytes": 256 << 10},
        "data": {"source": "backup_images", "users": 2,
                 "image_bytes": 128 << 10, "churn": 0.03,
                 "spot_bytes": 4096}},
}
TRAFFIC = {
    "tiny-upload": {"kind": "put_rounds", "clients": 4, "setup_rounds": 0,
                    "warm_rounds_min": 1, "warm_rounds_max": 3,
                    "warm_engine_batches": {"hash_chunks": [1, 4],
                                            "encode_blobs_multi": [8]},
                    "warm_engine_draws": 2, "check_fraction": 0.5},
    "tiny-nightly": {"kind": "put_rounds", "clients": 2, "setup_rounds": 1,
                     "warm_rounds_min": 1, "warm_rounds_max": 3,
                     "warm_engine_batches": {"hash_chunks": [2],
                                             "encode_blobs_multi": [4]},
                     "warm_engine_draws": 1, "check_fraction": 1.0},
    "tiny-get": {"kind": "open_get", "clients": 4, "prefill_rounds": 2,
                 "kill_nodes": [0], "rate_per_s": 20.0, "arrival_order": 1,
                 "warm_batches": [1, 2], "warm_passes_min": 1,
                 "warm_passes_max": 3},
}
CELLS = {"tiny.upload": ("tiny-rt", "tiny-upload"),
         "tiny.nightly": ("tiny-backup", "tiny-nightly"),
         "tiny.get": ("tiny-rt", "tiny-get")}


def make(tmp: Path) -> tuple[Path, dict]:
    """Copy the benchmark into ``tmp`` and add the small cells.

    Returns the copy's directory and a benchmark description whose
    metrics also list the small cells.
    """
    root = tmp / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for kind, table in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        for name, body in table.items():
            (root / kind / f"{name}.json").write_text(json.dumps(body))
    for cell, (config, traffic) in CELLS.items():
        (root / "workloads" / f"{cell}.json").write_text(json.dumps(
            {"config": config, "traffic": traffic, "chips": 1}))
    benchmark = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for group in ("end_to_end", "per_layer"):
        for m in benchmark[group]:
            cells = m.get("workloads")
            if cells is None:
                continue
            if "rt.upload" in cells:
                cells += ["tiny.upload", "tiny.nightly"]
            if "rt.get_degraded" in cells:
                cells.append("tiny.get")
    return root, benchmark
