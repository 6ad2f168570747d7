"""`python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`:
one run of one cell of BENCHMARK.json. The last line of stdout is the result object;
set-up items, window and cycle times and each compared number beside its limit go to
stderr. Exits non-zero, with no result, where JAX finds no TPU."""

import time

T_START = time.perf_counter()  # set-up is counted from here: before any heavy import

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
