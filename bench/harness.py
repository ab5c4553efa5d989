"""The benchmark harness: resolve a cell by name, set it up, time its
window, trace it on request, check its answers against the reference, and
print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by the name given in ``BENCHMARK.json``:

  * the configuration: the ``file`` of its ``configs`` entry;
  * the traffic mix: ``bench/traffic/<traffic>.json``, whose ``runner``
    names ``bench/runners/<runner>.py``, the code that drives the program;
  * the limits of the comparison: ``bench/limits/<cell>.json``;
  * a per-layer metric: ``bench/metrics/<metric>.py``, whose ``read(ctx)``
    returns the value or None when it finds nothing to read.

A runner module provides ``setup(config, traffic, seed, trace)``
(generation and warm-up through the program; returns the cell's state),
``call(state, j)`` (one unit of work through the program's entry point,
blocked on its results; returns {"events", "expected", "records"}),
``reference_tasks(state, calls)`` (the reference replays that judge the
records), ``work(state)`` (the shapes the roofline counts from) and
``release(state)``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))


class CellError(RuntimeError):
    """A cell, or one of its files, cannot be resolved."""


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str) -> ModuleType:
    if not os.path.exists(path):
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    runner: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType]


def resolve(root: str, name: str) -> Cell:
    """Find the cell ``name`` of ``<root>/BENCHMARK.json`` and every file it
    names; raises CellError for anything missing."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise CellError(f"no BENCHMARK.json at {root}")
    spec = load_json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"cell {name!r}: no configuration {w['config']!r}")
    cfg_path = os.path.join(root, configs[w["config"]]["file"])
    if not os.path.exists(cfg_path):
        raise CellError(f"missing file {cfg_path}")
    traffic_path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
    limits_path = os.path.join(BENCH, "limits", name + ".json")
    for p in (traffic_path, limits_path):
        if not os.path.exists(p):
            raise CellError(f"missing file {p}")
    traffic = load_json(traffic_path)
    runner = load_module(os.path.join(BENCH, "runners",
                                      traffic["runner"] + ".py"),
                         "bench_runner_" + traffic["runner"])
    per_layer = [m for m in spec["per_layer"] if applies(m, name)]
    readers = {m["name"]: load_module(
        os.path.join(BENCH, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_"))
        for m in per_layer}
    return Cell(name, int(w["chips"]), load_json(cfg_path), traffic,
                load_json(limits_path), runner,
                [m for m in spec["end_to_end"] if applies(m, name)],
                per_layer, readers)


# ---------------------------------------------------------------- the run

class CompileCounter:
    """Counts JAX's traces and backend compiles (persistent-cache reads
    included) from its monitoring events."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1


WATCHED = ("sweep.overflow_rungs", "sweep.jit_trace",
           "stream.pool_growths", "stream.overflow_rungs")


def _reference(tasks, workers: int):
    """Run the reference replays, in spawned processes when ``workers``."""
    from bench import reference
    if not workers:
        return [reference.replay_task(t) for t in tasks]
    import concurrent.futures as cf
    import multiprocessing as mp
    with cf.ProcessPoolExecutor(workers,
                                mp_context=mp.get_context("spawn")) as ex:
        return list(ex.map(reference.replay_task, tasks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, ref_workers: int = 0,
             log=sys.stderr) -> dict:
    """Set the cell up, measure its window, and judge it.  Returns the
    result object (the last line of the run); device checks are the
    caller's."""
    import jax
    from repro import obs
    from bench import check, reduce, roofline

    drv = cell.runner
    clock = CompileCounter()
    state = drv.setup(cell.config, cell.traffic, seed, trace)
    c0 = obs.counters()
    n0 = (clock.traces, clock.compiles)
    tracedir = None
    # a traced run traces a short window of its own, the traffic's
    # trace_calls calls: the per-event replay puts a hundred thousand to a
    # million operations a second into the device trace, and the
    # profiler's buffers hold about six million
    last = cell.traffic["trace_calls"] if trace else None
    if trace:
        obs.reset(counters_too=False)
        obs.enable()
        tracedir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tracedir)
    t_first = time.perf_counter()
    setup_s = t_first - t_start
    calls = []
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                calls.append(drv.call(state, len(calls)))
            if len(calls) == last or (
                    last is None and time.perf_counter() - t_first >= seconds):
                break
    window_s = time.perf_counter() - t_first
    spans = []
    if trace:
        jax.profiler.stop_trace()
        spans = obs.events()
        obs.disable()
    moved = obs.counter_deltas(c0)
    in_window = {"traces": clock.traces - n0[0],
                 "compiles": clock.compiles - n0[1]}
    in_window.update({k: moved.get(k, 0) for k in WATCHED})
    mem = 0
    for d in devices:
        stats = d.memory_stats() or {}
        mem = max(mem, int(stats.get("peak_bytes_in_use", 0)))
    work = drv.work(state)
    keys, tasks = drv.reference_tasks(state, calls)
    drv.release(state)
    del state

    events = sum(c["events"] for c in calls)
    rate = events / window_s
    e2e = {"setup_s": setup_s, cell.traffic["rate_metric"]: rate}
    metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end if m["name"] in e2e}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    print(f"# cell {cell.name} seed {seed}: {len(calls)} calls, "
          f"{events} events in {window_s} s, set-up {setup_s} s", file=log)
    print("# in the window: " + " ".join(f"{k}={v}" for k, v in
                                          in_window.items()), file=log)
    result = {"correct": False, "attempted": 0, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        red = reduce.reduce_dir(tracedir)
        shutil.rmtree(tracedir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"kind": cell.traffic["runner"], "spans": spans,
               "window_s": window_s, "trace": red,
               "work": roofline.scale(work, len(calls)),
               "device_kind": dev.device_kind, "log": log}
        per = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                per[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = per
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        print(f"# trace: busy {red['busy_s']} s of {red['window_s']} s, "
              f"{red['n_ops']} device ops"
              + (", EVENTS DROPPED: device metrics left out"
                 if red["dropped"] else ""), file=log)
    refs = _reference(tasks, ref_workers)
    verdict = check.compare(calls, dict(zip(keys, refs)), cell.limits)
    result["correct"] = verdict["correct"]
    result["attempted"] = verdict["attempted"]
    result["failed"] = verdict["failed"]
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=log)
    return result


def _environment(root: str) -> None:
    """Keep every cache and log inside the checkout or TMPDIR."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        root, ".bench_cache", "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the program's own profiler hook must stay off: start_trace cannot
    # nest, and the benchmark traces its own window
    os.environ.pop("REPRO_OBS_PROFILE", None)


def main(argv=None, *, root: str, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(root, args.workload)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    _environment(root)
    try:
        import jax
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devs[0].platform!r}); "
              "the benchmark only runs on the chip", file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1
    workers = max(1, min(8, (os.cpu_count() or 2) - 1))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devs[:cell.chips], ref_workers=workers)
    print(json.dumps(result))
    return 0
