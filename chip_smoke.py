#!/usr/bin/env python3
"""Chip smoke: drive the placement engine's main paths once on a TPU.

    python chip_smoke.py             # one chip: phases (a) suite, (b) stream,
                                     # (c) serve
    python chip_smoke.py --chips 4   # four chips: the suite grid sharded over
                                     # the chips vs shard="never", nothing else

Phases, all pinned to the native Pallas kernels (``backend="pallas"``):

  (a) suite  - ``api.Experiment`` over the Azure-like suite (28 instances x
               5000 requests, seed 2026), one policy per carry family, under
               clairvoyant and log-normal(1.0) predictions; once on the
               per-event kernel path and once event-blocked.  Records must
               equal a ``backend="jnp"`` run on the same chip.  Two lanes per
               policy are replayed again in an fp32-exact quantization (sizes
               on a 1/64 grid, times on a 64-second grid) and must match the
               host oracle (``core.engine``) exactly: bins equal, usage within
               1e-3.  The raw suite is not fp32-exact, so there the f32
               replay and the f64 oracle may split near-ties.
  (b) stream - one lane of 200,000 requests (5.56M / 28, the published
               per-instance size) streamed through ``replay_stream`` with the
               event-blocked kernel; usage, opened bins and every placement
               must be bit-identical to the ``backend="jnp"`` stream.
  (c) serve  - 20,000 Poisson requests through ``serve_traffic`` for a score
               and a category policy; every request answered, placements
               equal to the sequential host scheduler's.

Exits non-zero when no TPU is found, when a parity check fails, when any
``resilience.degrade_*`` counter moved, or when a call resolved to the
interpreter or jnp where the kernel was asked for.  Times printed here are
smoke times (compile and wall seconds of one run), not benchmarks.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SUITE = dict(family="azure", n_instances=28, n_items=5000, seed=2026)
POLICIES = ("best_fit_linf", "cbd", "hybrid", "rcp", "la_binary", "adaptive")
BLOCK_EVENTS = 256
ORACLE_LANES = 2
STREAM = dict(n_items=200_000, d=5, seed=0, policy="best_fit_linf",
              chunk_events=4096, item_rows=4096, max_bins=512)
SERVE = dict(n=20_000, rate=5e4, tps=1.2e5, sigma_pred=0.3, seed=0,
             policies=(("best_fit_linf", "best_fit", {"norm": "linf"}),
                       ("cbd", "cbd", {"beta": 2.0})))


class SmokeFailure(Exception):
    """A phase's parity or engine check failed."""


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (persistent-cache
    reads included), from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def quantized(inst):
    """fp32-exact copy of an instance: sizes on a 1/64 grid, times on a
    64-second grid, so every sum the f32 replay makes is exact below
    2^30 seconds and its decisions must equal the f64 oracle's."""
    import numpy as np

    from repro.core import Instance
    arr = np.round(inst.arrivals / 64.0) * 64.0
    dep = np.maximum(np.round(inst.departures / 64.0) * 64.0, arr + 64.0)
    sizes = np.maximum(np.round(inst.sizes * 64.0), 1.0) / 64.0
    return Instance(sizes, arr, dep, inst.name + "_q").sorted_by_arrival()


def _records(res):
    return {k: (r["usage_time"], r["n_bins_opened"])
            for k, r in res.records.items()}


def _diff(a, b):
    """The records on which ``a`` and ``b`` differ, with both values."""
    return [(k, a.get(k), b.get(k)) for k in sorted(set(a) | set(b))
            if a.get(k) != b.get(k)]


def phase_suite(backend: str = "pallas", suite=SUITE, policies=POLICIES,
                block_events: int = BLOCK_EVENTS,
                oracle_lanes: int = ORACLE_LANES, shard: str = "auto"):
    """(a): per-event and event-blocked kernel records equal jnp's, and the
    quantized lanes equal the host oracle.  Returns (events, verdict)."""
    from repro import api
    from repro.core import run
    from repro.core.jaxsim import host_algorithm
    from repro.data import make_azure_like_suite
    from repro.sweep.grid import PredModel

    settings = (api.Setting.clairvoyant(),
                api.Setting.predicted("lognormal", 1.0))
    wl = api.synthetic(suite["family"], suite["n_instances"],
                       suite["n_items"], seed=suite["seed"])
    exp = api.Experiment(wl, policies=policies, settings=settings)
    ref = _records(exp.run(backend="jnp", shard=shard))
    for label, be in (("per-event", 0), ("blocked", block_events)):
        got = _records(exp.run(backend=backend, block_events=be,
                               shard=shard))
        bad = _diff(ref, got)
        _check(not bad, f"suite {label} != jnp on {len(bad)} records, "
                        f"first {bad[:3]}")
    insts = make_azure_like_suite(suite["n_instances"], suite["n_items"],
                                  suite["seed"])
    q = [quantized(i) for i in insts[:oracle_lanes]]
    qexp = api.Experiment(api.instances(q, name="azure_q"),
                          policies=policies, settings=settings)
    preds = {"clairvoyant": None,
             "lognormal1": PredModel("lognormal", 1.0)}
    host = {}
    for label, be in (("per-event", 0), ("blocked", block_events)):
        for r in qexp.run(backend=backend, block_events=be,
                          shard=shard).rows():
            key = (r["policy"], r["pred"], r["instance"])
            if key not in host:
                inst = next(i for i in q if i.name == r["instance"])
                pm = preds[r["pred"]]
                pdur = None if pm is None else pm.durations(inst, (0,))[0]
                host[key] = run(inst, host_algorithm(r["policy"]),
                                predicted_durations=pdur)
            h = host[key]
            _check(h.n_bins_opened == r["n_bins_opened"] and
                   abs(h.usage_time - r["usage_time"]) <= 1e-3,
                   f"oracle {label} {r['policy']}/{r['pred']}/"
                   f"{r['instance']}: bins {r['n_bins_opened']} vs "
                   f"{h.n_bins_opened}, usage {r['usage_time']} vs "
                   f"{h.usage_time}")
    n_items = sum(i.n_items for i in insts)
    events = 2 * n_items * len(policies) * len(settings) * 3
    return events, (f"per-event == blocked == jnp on {len(ref)} records; "
                    f"{oracle_lanes} quantized lanes x {len(policies)} "
                    f"policies x 2 settings == host oracle")


def phase_shard(backend: str = "pallas", suite=SUITE, policies=POLICIES,
                block_events: int = 0):
    """--chips 4: the suite grid sharded over every chip equals the same
    grid on one chip (``shard="never"``)."""
    import jax

    from repro import api
    _check(jax.local_device_count() > 1, "sharding needs several devices")
    settings = (api.Setting.clairvoyant(),
                api.Setting.predicted("lognormal", 1.0))
    wl = api.synthetic(suite["family"], suite["n_instances"],
                       suite["n_items"], seed=suite["seed"])
    exp = api.Experiment(wl, policies=policies, settings=settings)
    sharded = _records(exp.run(backend=backend, block_events=block_events,
                               shard="always"))
    single = _records(exp.run(backend=backend, block_events=block_events,
                              shard="never"))
    bad = _diff(sharded, single)
    _check(not bad, f"sharded != single on {len(bad)} records, "
                    f"first {bad[:3]}")
    events = 2 * suite["n_instances"] * suite["n_items"] * \
        len(policies) * len(settings) * 2
    return events, (f"sharded over {jax.local_device_count()} chips == "
                    f"shard='never' on {len(single)} records")


def phase_stream(backend: str = "pallas", cfg=STREAM,
                 block_events: int = BLOCK_EVENTS):
    """(b): the event-blocked streamed replay equals the jnp stream."""
    import numpy as np

    from repro.stream import replay_stream, synthetic_source

    def once(be, T):
        src = synthetic_source(cfg["n_items"], d=cfg["d"], seed=cfg["seed"])
        return replay_stream(src, cfg["policy"],
                             chunk_events=cfg["chunk_events"],
                             item_rows=cfg["item_rows"],
                             max_bins=cfg["max_bins"], backend=be,
                             block_events=T, collect_placements=True)

    got = once(backend, block_events)
    ref = once("jnp", 0)
    _check(got.usage == ref.usage and got.opened == ref.opened,
           f"stream usage/opened {got.usage}/{got.opened} != jnp "
           f"{ref.usage}/{ref.opened}")
    _check(np.array_equal(got.placements, ref.placements),
           f"stream placements differ on "
           f"{int((got.placements != ref.placements).sum())} items")
    _check((got.placements >= 0).all(), "stream left items unplaced")
    return 2 * got.n_items, (f"{got.n_items} placements, usage "
                             f"{got.usage!r}, {got.opened} bins opened: "
                             "bit-identical to jnp")


def _host_placements(reqs, policy, kwargs, caps, tps):
    """The sequential host scheduler: one place per request at its
    arrival, departures replayed in finish-time order."""
    from repro.serving.scheduler import DVBPScheduler
    sched = DVBPScheduler(policy, caps, kwargs, tokens_per_second=tps)
    heap, placements = [], {}
    for r in sorted(reqs, key=lambda x: x.arrival):
        while heap and heap[0][0] <= r.arrival:
            ft, rid = heapq.heappop(heap)
            sched.finish(rid, ft)
        placements[r.rid] = sched.place(r, r.arrival)
        heapq.heappush(heap, (r.arrival + r.decode_len / tps, r.rid))
    return placements


def phase_serve(backend: str = "pallas", cfg=SERVE):
    """(c): the batched front end answers every request and places each
    exactly where the sequential host scheduler does."""
    from repro.serving.dispatch import serve_traffic
    from repro.serving.scheduler import ReplicaCapacity
    from repro.serving.traffic import make_traffic

    caps = ReplicaCapacity()
    reqs = make_traffic("poisson", cfg["n"], seed=cfg["seed"],
                        rate=cfg["rate"], sigma_pred=cfg["sigma_pred"])
    placed, verdicts = 0, []
    for kpol, hpol, kw in cfg["policies"]:
        rep = serve_traffic(reqs, kpol, caps, tps=cfg["tps"], impl=backend)
        _check(rep.placed == len(reqs) and rep.shed == 0,
               f"serve {kpol}: placed {rep.placed} of {len(reqs)}, "
               f"shed {rep.shed}")
        host = _host_placements(reqs, hpol, kw, caps, cfg["tps"])
        bad = sum(rep.placements.get(r) != host[r] for r in host)
        _check(bad == 0, f"serve {kpol}: {bad} placements differ from the "
                         "sequential host scheduler")
        placed += rep.placed
        verdicts.append(f"{kpol}: {rep.placed}/{len(reqs)} placed, "
                        f"{rep.replicas_opened} replicas, == host")
    return placed, "; ".join(verdicts)


def run_phase(name, fn, clock, dev_line):
    from repro import obs
    c0, s0, t0 = obs.counters(), clock.seconds, time.perf_counter()
    events, verdict = fn()
    wall = time.perf_counter() - t0
    moved = {k: v for k, v in obs.counter_deltas(c0).items()
             if (k.startswith("resilience.degrade") or
                 k in ("serving.select_pallas_interpret",
                       "serving.select_jnp")) and v}
    print(f"[{name}] device: {dev_line}")
    print(f"[{name}] smoke time, not a benchmark: compile "
          f"{clock.seconds - s0:.1f} s, wall {wall:.1f} s")
    print(f"[{name}] events placed: {events}")
    _check(not moved, f"{name}: fallback counters moved: {moved}")
    print(f"[{name}] parity: PASS - {verdict}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded suite grid vs shard="
                         "'never' (needs a four-chip host)")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e}); run it from a checkout", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke only runs on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable
    from repro.core.jaxsim import resolve_backend
    from repro.kernels.ops import resolved_select_impl
    cache = enable()
    _check(resolve_backend("pallas") == "pallas" and
           resolved_select_impl("pallas", block=True) == "pallas",
           "the kernel backend does not resolve to the native kernel")
    dev_line = (f"platform={dev.platform} kind={dev.device_kind} "
                f"count={len(devs)}")
    print(f"# {dev_line}; compile cache {cache}", flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phases = [("shard", phase_shard)]
    else:
        phases = [("suite", phase_suite), ("stream", phase_stream),
                  ("serve", phase_serve)]
    try:
        for name, fn in phases:
            run_phase(name, fn, clock, dev_line)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL - {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
