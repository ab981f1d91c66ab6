#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (see ``chipbench/bench.py``).  The run
fails, printing no result, unless the first device is a TPU and there are
as many chips as the cell asks for.  The last line of standard output is
the result object; the numbers that decide ``correct`` are also the last
lines of standard error.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
