# The paper's figures: one function per figure (benchmarks/figures.py),
# printed as ``name,us_per_call,derived`` CSV rows where `derived` is the
# figure's metric, the mean performance ratio over the instance suite.
#
#   PYTHONPATH=src python -m benchmarks.run [--only figN] [--fast]
#   Scale knobs: BENCH_INSTANCES / BENCH_ITEMS / BENCH_REPEATS env vars.
#
# --fast: small suites and a figure subset, a quick end-to-end check.
# Speed is measured by bench/run.py on the chip, not here.
from __future__ import annotations

import argparse
import os
import sys
import time

FAST_FIGURES = ("fig2", "fig5")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args(argv)

    if args.fast:
        os.environ.setdefault("BENCH_INSTANCES", "4")
        os.environ.setdefault("BENCH_ITEMS", "300")
        os.environ.setdefault("BENCH_REPEATS", "1")

    from repro.compile_cache import enable
    enable()
    from . import figures

    print("name,us_per_call,derived")
    t0 = time.time()
    for fn in figures.ALL_FIGURES:
        if args.only and args.only not in fn.__name__:
            continue
        if args.fast and not args.only and \
                not fn.__name__.startswith(FAST_FIGURES):
            continue
        for line in fn():
            print(line, flush=True)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
