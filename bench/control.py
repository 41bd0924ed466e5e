"""Read a cell's compared numbers over many seeds, sound and faulted.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 --faults sound,parity_zeroed

Runs in one process (the chip is set up once): for every seed, one run of
the cell for each fault (``sound`` is the program as it is), at the cell's own
size with a short window.  Prints one JSON line per run with ``correct``
and every compared number, then a summary: the largest number any sound
run read and the smallest any faulted run read.  The benchmark's own runs
never plant a fault; this is how the limits and their controls were read.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="sound")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    faults = [None if f == "sound" else f for f in args.faults.split(",")]
    sound_max: dict = {}
    fault_min: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in faults:
            t0 = time.perf_counter()
            out = harness.run_cell(cell, seed, args.seconds, False,
                                   benchmark=benchmark, started=t0,
                                   fault=fault, log=lambda s: None)
            checks = {n: c["value"] for n, c in out["checks"].items()}
            print(json.dumps({"seed": seed, "fault": fault or "sound",
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": checks,
                              "metrics": {n: m["value"] for n, m in
                                          out["metrics"].items()},
                              "run_s": time.perf_counter() - t0}),
                  flush=True)
            table = sound_max if fault is None else fault_min
            for n, v in checks.items():
                key = n if fault is None else f"{fault}:{n}"
                pick = max if fault is None else min
                table[key] = pick(table.get(key, v), v)
    print(json.dumps({"sound_max": sound_max, "fault_min": fault_min}))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.exit(main())
