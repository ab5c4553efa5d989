#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` finds
each one's file by its name.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.

This file stays free of imports beyond the standard library: the reference
runs in spawned worker processes, which import it again.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    return harness.main(argv, root=ROOT, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
