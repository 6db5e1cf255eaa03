#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are listed in BENCHMARK.json. The last line of standard output is
the result as one JSON object; the numbers ``correct`` compared, each with
its limit, are the last lines of standard error. Without a TPU, or with
fewer chips than the cell needs, it exits 3 and prints no result.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
