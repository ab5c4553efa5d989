#!/usr/bin/env python3
"""Readings of the control at a cell's own size: the reference put in the
program's place and computed in a lower precision, judged by the cell's
comparison against the float64 reference.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed with the numbers compared and their limits.
Pure NumPy: it imports neither JAX nor the program, and the benchmark's
own runs never run it.  ``PERF.md`` keeps the readings that each limit in
``bench/limits/`` was set from.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL = "bfloat16"      # the precision below the float32 the cells state


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from bench import check, harness, reference
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(ROOT, args.workload)
    import concurrent.futures as cf
    import multiprocessing as mp
    seeds = [int(s) for s in args.seeds.split(",")]
    lanes = {seed: cell.runner.lanes(cell.config, cell.traffic, seed)
             for seed in seeds}
    tasks = [(cell.traffic["policy"], raw, pd, dt) for seed in seeds
             for dt in ("float64", CONTROL)
             for _, raw, pd in lanes[seed]]
    workers = max(1, min(len(tasks), (os.cpu_count() or 2) - 1))
    with cf.ProcessPoolExecutor(workers,
                                mp_context=mp.get_context("spawn")) as ex:
        out = iter(list(ex.map(reference.replay_task, tasks)))
    for seed in seeds:
        keys = [k for k, _, _ in lanes[seed]]
        ref = {k: next(out) for k in keys}
        got = {k: next(out)[:2] for k in keys}
        v = check.compare([{"expected": keys, "records": got}], ref,
                          cell.limits)
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "dtype": CONTROL, "correct": v["correct"],
                          "failed": v["failed"],
                          "attempted": v["attempted"],
                          "checks": v["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
