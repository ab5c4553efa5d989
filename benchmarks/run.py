# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows; `derived` is the paper's metric (mean performance ratio) for
# figure benches, throughput/quality for perf benches.
#
#   PYTHONPATH=src python -m benchmarks.run [--only figN] [--skip-perf]
#       [--fast] [--json BENCH_sweep.json]
#   Scale knobs: BENCH_INSTANCES / BENCH_ITEMS / BENCH_REPEATS env vars.
#
# --fast: smoke mode (small suites, a figure subset, a small sweep grid) -
#   used by tests/test_benchmarks_smoke.py to keep the benches runnable.
# --json PATH: also emit every row as machine-readable JSON
#   [{"name", "us_per_call", "derived", "median_us", "stdev_us", "reps"},
#   ...] plus the run's obs counter snapshot, so the perf trajectory can
#   be tracked across PRs (see BENCH_sweep.json at the repo root).  Every
#   row carries the full timing block: obs.timeit rows parse it from
#   their spread comment, one-shot wall-clock rows normalize to
#   median_us=us_per_call / stdev_us=0 / reps=1.  Rows timed through
#   Pallas interpret-mode emulation on CPU additionally carry
#   "mode": "interpret" - exclude them from speedup-style comparisons.  A JSONL obs run log (spans +
#   counters, ``repro.obs.export_jsonl``) is written next to it as
#   PATH-with-.obs.jsonl - the per-SHA CI artifact; inspect with
#   ``python -m repro obs``.
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

FAST_FIGURES = ("fig2", "fig5")

# the spread comment obs.TimingStats.row() appends to repeated-timing rows
_SPREAD_RE = re.compile(
    r"med=(?P<med>[\d.]+)us\s+sd=(?P<sd>[\d.]+)us\s+n=(?P<n>\d+)")


def _parse_row(line: str):
    head, _, comment = line.partition("#")
    head = head.strip().rstrip(",")
    parts = head.split(",")
    if len(parts) != 3:
        return None
    try:
        row = {"name": parts[0], "us_per_call": float(parts[1]),
               "derived": float(parts[2])}
    except ValueError:
        return None
    m = _SPREAD_RE.search(comment)
    if m:   # obs.timeit rows carry their spread as a structured comment
        row.update(median_us=float(m.group("med")),
                   stdev_us=float(m.group("sd")), reps=int(m.group("n")))
    else:   # one-shot wall-clock rows: normalize to the same schema
        row.update(median_us=row["us_per_call"], stdev_us=0.0, reps=1)
    if "mode=interpret" in comment:
        # Pallas rows emulated on CPU: tagged so CI tooling excludes them
        # from speedup-style comparisons (the timing measures the
        # interpreter, not the kernel)
        row["mode"] = "interpret"
    return row


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--skip-perf", action="store_true")
    ap.add_argument("--skip-figures", action="store_true")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default="", metavar="PATH")
    args = ap.parse_args(argv)

    if args.fast:   # must happen before benchmarks.common is imported
        os.environ.setdefault("BENCH_INSTANCES", "4")
        os.environ.setdefault("BENCH_ITEMS", "300")
        os.environ.setdefault("BENCH_REPEATS", "1")

    from repro import obs
    from repro.compile_cache import enable
    enable()
    if args.json:
        # record spans for the run log riding next to the JSON artifact
        obs.reset(counters_too=False)
        obs.enable()

    rows = []

    def emit(line: str) -> None:
        print(line, flush=True)
        parsed = _parse_row(line)
        if parsed:
            rows.append(parsed)

    print("name,us_per_call,derived")
    t0 = time.time()
    if not args.skip_figures:
        from . import figures
        for fn in figures.ALL_FIGURES:
            if args.only and args.only not in fn.__name__:
                continue
            if args.fast and not args.only and \
                    not fn.__name__.startswith(FAST_FIGURES):
                continue
            for line in fn():
                emit(line)
    if not args.skip_perf and not args.only:
        from . import perf
        # sweep_grid runs before the interpret-mode kernel benches: their
        # emulation programs bloat the in-process XLA state enough to skew
        # the headline sweep timing (which includes compilation).
        groups = [perf.kernels, perf.jaxsim_vs_oracle, perf.serving_fleet,
                  perf.sweep_grid, perf.api_facade, perf.sweep_categories,
                  perf.consolidate_sweep,
                  perf.obs_overhead, perf.resilience_overhead,
                  perf.sweep_retrace,
                  perf.replay_carry, perf.fitscore_step, perf.replay_block,
                  perf.replay_block_bytes, perf.sweep_sharded,
                  perf.serve_throughput, perf.serve_retrace,
                  perf.stream_replay,
                  perf.roofline_summary]
        if args.fast:
            # sweep_batched_only re-times the full-size headline row
            # (perf/sweep_batched_28x4) without the slow loop baseline -
            # CI gates on it against the committed BENCH_sweep.json.
            groups = [lambda: perf.sweep_grid(n_instances=6, n_items=120,
                                              policies=("first_fit",
                                                        "greedy")),
                      perf.sweep_batched_only,
                      # same grid/policies as sweep_batched_only, so the
                      # full-size facade row rides its compile cache
                      perf.api_facade,
                      # ... as do the obs/resilience-overhead and
                      # retrace-gate rows
                      perf.obs_overhead, perf.resilience_overhead,
                      perf.sweep_retrace,
                      lambda: perf.sweep_categories(n_instances=6,
                                                    n_items=120,
                                                    policies=("cbd",
                                                              "la_binary"),
                                                    seeds=(0, 1)),
                      # consolidation rows ride the fast JSON so CI can
                      # gate their presence + the disabled-path usage
                      lambda: perf.consolidate_sweep(
                          n_instances=6, n_items=120,
                          policies=("first_fit", "greedy"),
                          thresholds=(0.25,)),
                      perf.replay_carry,
                      lambda: perf.fitscore_step(lanes=2, n_slots=512),
                      # the event-blocked replay rows ride the fast JSON
                      # artifact so CI tracks them per push
                      lambda: perf.replay_block(lanes=2, n_items=60),
                      lambda: perf.replay_block_bytes(lanes=2, n_items=30),
                      # batched-admission rows ride the fast JSON too:
                      # CI gates throughput scaling, latency and the
                      # serve retrace invariant per push
                      lambda: perf.serve_throughput(n=480),
                      perf.serve_retrace,
                      # the streamed-replay smoke row: bit-equality +
                      # bounded-memory gates run before the number is
                      # emitted, and the row rides the per-SHA artifact
                      perf.stream_replay_fast]
        for group in groups:
            try:
                for line in group():
                    emit(line)
            except Exception as e:   # keep the harness robust
                print(f"# {getattr(group, '__name__', 'group')} failed: {e}",
                      file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)

    if args.json:
        counters = obs.counters()
        with open(args.json, "w") as f:
            json.dump({"rows": rows,
                       "counters": counters,
                       "env": {k: os.environ[k] for k in
                               ("BENCH_INSTANCES", "BENCH_ITEMS",
                                "BENCH_REPEATS") if k in os.environ}},
                      f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)
        log = os.path.splitext(args.json)[0] + ".obs.jsonl"
        obs.export_jsonl(log, obs.events(), counters,
                         meta={"tool": "benchmarks.run",
                               "fast": bool(args.fast), "n_rows": len(rows)})
        print(f"# wrote obs run log to {log}", file=sys.stderr)


if __name__ == "__main__":
    main()
