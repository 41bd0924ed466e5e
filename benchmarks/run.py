"""Benchmark runner: one module per paper table/figure + kernels.

Prints ``name,us_per_call,derived`` CSV rows (per the harness contract)
and writes the full JSON to results/bench.json.  Each module also ships a
``check()`` asserting the paper's qualitative claims -- failures are
reported and exit non-zero.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


MODULES = [
    "fig3a_kn_dedup",
    "fig3b_kn_latency",
    "fig3c_dedup_time",
    "fig3d_retrieval_load",
    "headline_3mb",
    "pipeline_bench",
    "scheduler_bench",
    "shard_bench",
    "repair_bench",
    "disaster_bench",
    "slo_bench",
    "class_bench",
    "kernel_bench",
    "checkpoint_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (slow); default is quick mode")
    ap.add_argument("--only", default="",
                    help="comma-separated module filter")
    ap.add_argument("--engine", default="", choices=("", "numpy", "kernel"),
                    help="data-plane coding engine for store benchmarks")
    ap.add_argument("--out", default="results/bench.json")
    args = ap.parse_args()

    only = set(args.only.split(",")) if args.only else None
    all_rows, all_fails = {}, []
    print("name,us_per_call,derived")
    for modname in MODULES:
        if only and modname not in only:
            continue
        mod = __import__(f"benchmarks.{modname}", fromlist=["run"])
        kwargs = {"quick": not args.full}
        if args.engine and "engine" in inspect.signature(mod.run).parameters:
            kwargs["engine"] = args.engine
        t0 = time.time()
        rows = mod.run(**kwargs)
        dt = time.time() - t0
        fails = mod.check(rows) if hasattr(mod, "check") else []
        all_rows[modname] = rows
        all_fails += [f"{modname}: {f}" for f in fails]
        for r in rows:
            us = r.get("us_per_call", round(dt * 1e6 / max(1, len(rows)), 1))
            derived = {k: v for k, v in r.items()
                       if k not in ("name", "us_per_call")}
            print(f"{r['name']},{us},\"{derived}\"")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"rows": all_rows, "failures": all_fails}, f, indent=1)
    if all_fails:
        print("\nPAPER-CLAIM CHECK FAILURES:", file=sys.stderr)
        for f_ in all_fails:
            print(" ", f_, file=sys.stderr)
        raise SystemExit(1)
    print(f"\nall paper-claim checks passed ({sum(len(r) for r in all_rows.values())} rows)")


if __name__ == "__main__":
    main()
