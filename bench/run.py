#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the TPU
chips the cell asks for (``BENCHMARK.json``). The last line of standard
output is the result object; the numbers the run's check compared, each
beside its limit, are the last lines of standard error. No TPU, too few
chips, or no program beside the benchmark: exit code 2 and no result.
"""

import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

harness.T_PROCESS = T_START

if __name__ == "__main__":
    sys.exit(harness.main())
