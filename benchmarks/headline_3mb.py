"""Headline claim (S IV): retrieving a 3 MB file takes ~2.5 s with
SEARS ULB(10,5) vs ~7 s from stock EC2 (single-stream download).

The latency model is *calibrated* on exactly these two anchors
(DESIGN.md S8), so this benchmark verifies the calibration closed and
reports the speedup the model then predicts across file sizes.

``--engine {numpy,kernel,fused}`` selects the data-plane coding engine;
all are byte-identical, so the modelled times do not depend on it.
Speed is measured on the chip by ``bench/run.py``, not here.
"""

from __future__ import annotations

import argparse

import numpy as np

try:
    from benchmarks.common import calibrated_params, make_store
except ImportError:  # invoked directly: python benchmarks/headline_3mb.py
    import os
    import sys
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)
    from benchmarks.common import calibrated_params, make_store


def run(quick: bool = True, engine: str = "numpy") -> list[dict]:
    params = calibrated_params()
    rows = []
    rng = np.random.default_rng(7)
    for mb in (1, 3, 10):
        nbytes = mb * 2**20
        single = float(np.mean([params.single_stream_time(nbytes, rng)
                                for _ in range(128)]))
        # end-to-end through the real store path (chunk/dedup/code/fetch)
        store = make_store("ulb", engine=engine)
        blob = np.random.default_rng(mb).integers(
            0, 256, size=nbytes, dtype=np.int64).astype(np.uint8).tobytes()
        store.put_file("u", f"f{mb}", blob)
        times = []
        n_iter = 16 if quick else 64
        for _ in range(n_iter):
            out, st = store.get_file("u", f"f{mb}")
            times.append(st.time_s)
        assert out == blob
        sears = float(np.mean(times))
        rows.append({"name": f"headline/{mb}MB", "mb": mb,
                     "engine": engine,
                     "sears_ulb_s": round(sears, 3),
                     "ec2_single_s": round(single, 3),
                     "speedup": round(single / sears, 2)})
    return rows


def check(rows: list[dict]) -> list[str]:
    fails = []
    r3 = next(r for r in rows if r["mb"] == 3)
    if not 2.0 <= r3["sears_ulb_s"] <= 3.2:
        fails.append(f"headline: 3MB ULB {r3['sears_ulb_s']}s, paper 2.5s")
    if not 6.0 <= r3["ec2_single_s"] <= 8.2:
        fails.append(f"headline: 3MB single {r3['ec2_single_s']}s, paper 7s")
    for r in rows:
        if r["speedup"] <= 1.5:
            fails.append(f"headline: speedup {r['speedup']} at {r['mb']}MB")
    return fails


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("numpy", "kernel", "fused"),
                    default="numpy", help="data-plane coding engine")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    result_rows = run(quick=not args.full, engine=args.engine)
    for r in result_rows:
        print(r)
    failures = check(result_rows)
    for f in failures:
        print("FAIL:", f)
    raise SystemExit(1 if failures else 0)
