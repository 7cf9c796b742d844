"""One run of one benchmark cell:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine that holds the chips the
cell asks for. The last line of standard output is the result's JSON
object; the numbers compared for ``correct`` are the last lines of
standard error. Without a TPU it exits 2 before any work.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
