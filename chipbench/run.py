"""Benchmark entry: one run of one cell on the chips attached to the host.

    python chipbench/run.py --workload sc2_3b.codegen --seed 7 --seconds 10 --trace 0

Prints the platform, device kind and count first (standard error), each
number that decides ``correct`` beside its limit as the last lines of
standard error, and one JSON result as the last line of standard output.
Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
