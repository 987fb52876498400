#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator JAX finds here.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output (see
``bench/harness.py``) and each compared number beside its limit as the
last lines of standard error.  Exits non-zero, printing no result, when
JAX finds no accelerator or fewer chips than the cell asks for, or when
the program under test is not beside the benchmark.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    import bench
    bench.prepare_process()
    from bench import harness
    sys.exit(harness.main(t_start=T_START))
