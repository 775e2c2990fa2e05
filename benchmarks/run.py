#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one cell of BENCHMARK.json, once, in this process. The last
line of standard output is the result object; see benchmarks/README.md."""

import time

PROCESS_START = time.perf_counter()

import os   # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == '__main__':
    import harness
    sys.exit(harness.main(sys.argv[1:], PROCESS_START))
