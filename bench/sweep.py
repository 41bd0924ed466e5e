"""Sweep an open-loop get cell's rate to find the highest it sustains.

    python3 bench/sweep.py --workload rt.get_degraded --seed 5 \
        --seconds 20 --rates 6,8,10,12

One process and one set-up; for each rate one window of the cell with
only the rate changed.  Prints per rate: gets sent, p50 and p95 latency,
and the backlog's growth: the median latency of the window's last quarter of
sends minus that of its first quarter.  A rate whose backlog grows is
above what the system sustains.  The cell's fixed rate was set once from
such a sweep, at about four fifths of the highest sustained rate.
"""

import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    from bench import harness
    from bench.traffic import percentile
    cell = harness.load_cell(args.workload)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = harness.Run(cell, args.seed, False, started=time.perf_counter())
    print(json.dumps({"setup_s": run.setup_s}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        w = run.window(args.seconds, rate=rate)
        out = run.result(w, benchmark)
        gets = sorted(w.gets, key=lambda g: g[1])
        lat = np.array([done - sent for _, sent, done, _, _ in gets])
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, "gets": len(lat),
            "correct": out["correct"],
            "p50_ms": 1e3 * percentile(lat, 50),
            "p95_ms": 1e3 * percentile(lat, 95),
            "growth_ms": 1e3 * float(np.median(lat[-q:])
                                     - np.median(lat[:q])),
            "gets_per_flush": run.sched_delta["n_requests"]
            / max(1, run.sched_delta["n_get_windows"]),
            "window_s": w.seconds}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.exit(main())
