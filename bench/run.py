#!/usr/bin/env python3
"""Benchmark entry point; see ``bench/harness/core.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds, and
prints one JSON object as the last line of standard output.  Exits
non-zero, with no result line, where there is no TPU, fewer chips than the
cell asks for, or the program is not beside the benchmark.
"""
import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.harness import core
    sys.exit(core.main(sys.argv[1:], T_PROCESS))
