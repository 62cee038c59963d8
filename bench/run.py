"""Run one benchmark cell of the fleet tuning service on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout; it puts ``src`` and the checkout on
``sys.path`` itself. The last line of standard output is the result object;
with no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the checkout and the program's sources; not bench/ itself, whose module
# names would shadow others
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != BENCH]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
