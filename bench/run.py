"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Needs a TPU: off one, or short of the cell's chips, it exits non-zero
without a result line.  The compile cache lives at ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

if __name__ == "__main__":
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != HERE]
    from bench.harness import main
    sys.exit(main())
