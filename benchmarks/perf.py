"""Micro/throughput benchmarks beyond the paper figures:

  * Pallas kernels (interpret mode on CPU; native on TPU) vs jnp references
  * core.jaxsim trace replay vs the Python oracle engine
  * serving fleet placement throughput
  * the obs layer's own overhead + the jit-retrace invariant as perf rows
  * roofline summary rows from the dry-run artifacts (experiments/dryrun)

Repeated timings go through ``obs.timeit`` (perf_counter, device-result
blocking, min/median/stdev) - the spread rides each CSV row as a
structured ``# med=..us sd=..us n=..`` comment that ``benchmarks/run.py``
parses into the bench JSON, so host-noise (the ±60% problem of raw
best-of-N ``time.time`` loops) is visible per row instead of silently
folded into the minimum.  One-shot cold timings (wall clock including
compile, by suite convention) use ``time.perf_counter`` directly.
"""
from __future__ import annotations

import glob
import json
import os
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def _timeit(fn, *args, n: int = 5) -> obs.TimingStats:
    """Shared repeated-timing helper: ``obs.timeit`` (one warmup rep for
    compile, then ``n`` blocked perf_counter reps)."""
    return obs.timeit(fn, *args, n=n, warmup=1)


def kernels() -> List[str]:
    import repro.kernels.ops as ops
    rows = []
    impl = "auto" if jax.default_backend() == "tpu" else "ref"
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (4, 256, 8, 64), jnp.float32)
    k = jax.random.normal(key, (4, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (4, 256, 2, 64), jnp.float32)
    st = _timeit(lambda: ops.flash_attention(q, k, v, impl=impl))
    flops = 4 * 256 * 256 * 8 * 64 * 2 * 2 / 2
    rows.append(st.row(f"perf/flash_attention_{impl}",
                       f"{flops / st.best / 1e9:.1f}"))

    qd = jax.random.normal(key, (8, 8, 64))
    kd = jax.random.normal(key, (8, 4096, 2, 64))
    vd = jax.random.normal(key, (8, 4096, 2, 64))
    kl = jnp.full((8,), 4096, jnp.int32)
    st = _timeit(lambda: ops.decode_attention(qd, kd, vd, kl, impl=impl))
    gb = 8 * 4096 * 2 * 64 * 4 * 2 / 1e9
    rows.append(st.row(f"perf/decode_attention_{impl}",
                       f"{gb / st.best:.1f}"))

    rem = jnp.asarray(np.random.default_rng(0).random((4096, 5)))
    alive = jnp.ones(4096, bool)
    item = jnp.asarray(np.random.default_rng(1).random(5) * 0.3)
    st = _timeit(lambda: ops.fitscore(rem, alive, item, impl=impl))
    rows.append(st.row(f"perf/fitscore_4096bins_{impl}",
                       f"{4096 / st.best / 1e6:.2f}"))
    return rows


def fitscore_step(lanes: int = 8, n_slots: int = 4096,
                  d: int = 5) -> List[str]:
    """The sweep scan's placement step in isolation: the inline vmapped jnp
    select vs the fused lane-batched Pallas kernel (interpret mode on CPU,
    native on TPU).  Derived column: scored slots per microsecond."""
    from functools import partial

    from repro.core.jaxsim import _select_slot
    from repro.kernels.fitscore import fitscore_select_batch
    rng = np.random.default_rng(0)
    loads = jnp.asarray(rng.random((lanes, n_slots, d)) * 0.5, jnp.float32)
    counts = jnp.asarray((rng.random((lanes, n_slots)) > 0.3)
                         .astype(np.int32))
    alive = counts > 0
    oseq = jnp.asarray(np.tile(rng.permutation(n_slots), (lanes, 1))
                       .astype(np.int32))
    closes = jnp.asarray(rng.random((lanes, n_slots)) * 1e4, jnp.float32)
    size = jnp.asarray(rng.random((lanes, d)) * 0.3, jnp.float32)
    pdep = jnp.asarray(rng.random(lanes) * 1e4, jnp.float32)
    now = jnp.asarray(rng.random(lanes) * 1e3, jnp.float32)
    dmask = jnp.ones((lanes, d))
    args = (loads, counts, alive, oseq, oseq, closes, size, pdep, now, dmask)
    policy = "best_fit_linf"

    jnp_fn = jax.jit(lambda *a: jax.vmap(partial(_select_slot, policy))(*a))
    st_j = _timeit(lambda: jnp_fn(*args))
    interpret = jax.default_backend() != "tpu"
    pal_fn = jax.jit(lambda *a: fitscore_select_batch(
        *a, policy=policy, interpret=interpret))
    st_p = _timeit(lambda: pal_fn(*args))
    per_us = lanes * n_slots / 1e6
    return [st_j.row("perf/fitscore_step_jnp",
                     f"{per_us / st_j.best:.2f}"),
            st_p.row("perf/fitscore_step_pallas",
                     f"{per_us / st_p.best:.2f}") + _interpret_tag()]


def replay_carry(lanes: int = 8, n_slots: int = 2048,
                 d: int = 5) -> List[str]:
    """The padded-carry refactor in isolation: the sweep scan used to
    re-pad its whole (slots, d) state into the kernel's (Np, dpad=128)
    layout on every event step (~25x redundant traffic at d=5); the carry
    now lives pre-padded across the scan.

    ``perf/replay_carry_repad``  - per-step select INCLUDING the state
    re-pad (the pre-refactor cost; derived column: GB re-padded per call).
    ``perf/replay_carry_padded`` - per-step select on the pre-padded carry
    (the new cost; derived column: speedup over the repad path).
    Measured on the jnp twin of the select so the comparison isolates data
    movement, not Pallas interpret overhead."""
    from functools import partial

    from repro.core.jaxsim import _select_slot
    from repro.kernels.fitscore import select_pad_geometry
    Np, dpad, _, _ = select_pad_geometry(n_slots, d)
    rng = np.random.default_rng(0)
    loads = jnp.asarray(rng.random((lanes, n_slots, d)) * 0.5, jnp.float32)
    counts = jnp.asarray((rng.random((lanes, n_slots)) > 0.3)
                         .astype(np.int32))
    oseq = jnp.asarray(np.tile(rng.permutation(n_slots), (lanes, 1))
                       .astype(np.int32))
    closes = jnp.asarray(rng.random((lanes, n_slots)) * 1e4, jnp.float32)
    size = jnp.asarray(rng.random((lanes, d)) * 0.3, jnp.float32)
    pdep = jnp.asarray(rng.random(lanes) * 1e4, jnp.float32)
    now = jnp.asarray(rng.random(lanes) * 1e3, jnp.float32)

    @jax.jit
    def pad_state(loads, counts, oseq, closes, size):
        f32, i32 = jnp.float32, jnp.int32
        return (jnp.zeros((lanes, Np, dpad), f32)
                .at[:, :n_slots, :d].set(loads),
                jnp.zeros((lanes, Np), i32).at[:, :n_slots].set(counts),
                jnp.zeros((lanes, Np), i32).at[:, :n_slots].set(oseq),
                jnp.full((lanes, Np), -1e30, f32)
                .at[:, :n_slots].set(closes),
                jnp.zeros((lanes, dpad), f32).at[:, :d].set(size))

    dmask_p = jnp.zeros((lanes, dpad), jnp.float32).at[:, :d].set(1.0)

    def select_padded(lp, cp, op, clp, sp):
        return jax.vmap(partial(_select_slot, "best_fit_linf"))(
            lp, cp, cp > 0, op, op, clp, sp, pdep, now, dmask_p, None)

    sel = jax.jit(select_padded)
    repad = jax.jit(lambda *a: select_padded(*pad_state(*a)))
    compact = (loads, counts, oseq, closes, size)
    st_repad = _timeit(lambda: repad(*compact))
    padded = jax.block_until_ready(pad_state(*compact))
    st_padded = _timeit(lambda: sel(*padded))
    gb = lanes * Np * (dpad + 3) * 4 / 1e9   # padded state written per step
    return [st_repad.row("perf/replay_carry_repad",
                         f"{gb / st_repad.best:.2f}"),
            st_padded.row("perf/replay_carry_padded",
                          f"{st_repad.best / st_padded.best:.2f}")]


def _quantized_suite(lanes: int, n_items: int, d: int, seed: int = 0):
    from repro.core import Instance
    rng = np.random.default_rng(seed)
    insts = []
    for s in range(lanes):
        sizes = rng.integers(1, 24, (n_items, d)) / 64.0
        arr = np.sort(rng.integers(0, 50000, n_items)).astype(float)
        dur = rng.integers(10, 5000, n_items).astype(float)
        insts.append(Instance(sizes, arr, arr + dur, f"b{s}")
                     .sorted_by_arrival())
    return insts


def replay_block(lanes: int = 4, n_items: int = 120, d: int = 3,
                 blocks=(8, 32)) -> List[str]:
    """The event-blocked replay megakernel vs the per-event kernel path,
    per event step (interpret mode on CPU, native on TPU).

    ``perf/replay_block_T=1`` is the per-event fused-select scan (the PR-2/3
    hot loop: one kernel launch + one full carry HBM round-trip per event);
    ``T=8`` / ``T=32`` run whole blocks on-chip.  Middle column: us per
    event step; derived column: speedup over the T=1 path (1.0 for the
    baseline row).  Usage totals are asserted identical across block sizes
    - the knob is execution-only."""
    from repro.sweep import pack_instances, run_batch
    batch = pack_instances(_quantized_suite(lanes, n_items, d))
    be = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    E = 2 * batch.n_max
    stats, usage = {}, {}
    for T in (1,) + tuple(blocks):
        kw = dict(max_bins=64, backend=be, block_events=T)
        usage[T] = float(run_batch(batch, "best_fit_linf", **kw)
                         .usage_time.sum())          # compile/warm
        # best-of-3 (min() discards contended reps), med/sd on the row
        stats[T] = obs.timeit(
            lambda: run_batch(batch, "best_fit_linf", **kw), n=3, warmup=0)
    assert len(set(usage.values())) == 1, usage
    t_step = {T: st.best / E for T, st in stats.items()}
    tag = _interpret_tag()
    rows = [stats[1].row("perf/replay_block_T=1", "1.00", scale=1 / E)
            + tag]
    rows += [stats[T].row(f"perf/replay_block_T={T}",
                          f"{t_step[1] / t_step[T]:.2f}", scale=1 / E)
             + tag for T in blocks]
    return rows


def replay_block_bytes(lanes: int = 2, n_items: int = 40, d: int = 3,
                       T: int = 8) -> List[str]:
    """Per-event-step HBM bytes moved by the compiled replay, from the
    trip-count-aware HLO cost model (``launch.hlo_cost.module_cost``): the
    per-event kernel path streams the whole padded carry through HBM once
    per event; the blocked path touches it once per T-event block.

    On a TPU the replay compiles with the native Pallas kernels, which
    appear in the HLO as opaque custom-calls - ``charge_custom_calls=True``
    counts their operand+result boundary (x the scan trip count), i.e. the
    carry's real HBM round-trips.  On CPU the interpret-mode lowering is
    plain HLO (no custom-calls; the flag is inert there), so the model
    counts the emulated kernel's slice/update traffic directly - a looser
    proxy, but the per-event-vs-blocked comparison is the same structural
    question: how often does the carry cross the HBM boundary.  Middle
    column: bytes per event step; derived: reduction factor vs per-event.
    Asserts the blocked path moves strictly less."""
    from functools import partial

    from repro.launch.hlo_cost import module_cost
    from repro.sweep import pack_instances
    from repro.sweep.runner import _simulate_lanes_impl
    batch = pack_instances(_quantized_suite(lanes, n_items, d))
    args = tuple(jnp.asarray(a) for a in
                 (batch.sizes, batch.times, batch.kinds, batch.items,
                  batch.pdeps, batch.dmask, batch.arrivals, batch.pdeps,
                  batch.n_items))
    E = batch.times.shape[1]
    be = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"

    def bytes_per_step(block):
        fn = jax.jit(partial(_simulate_lanes_impl, policy="best_fit_linf",
                             max_bins=32, backend=be, block_events=block))
        text = fn.lower(*args).compile().as_text()
        return module_cost(text, charge_custom_calls=True).bytes / E

    b_ev = bytes_per_step(0)
    b_blk = bytes_per_step(T)
    assert b_blk < b_ev, \
        f"blocked replay must move strictly fewer bytes: {b_blk} vs {b_ev}"
    tag = _interpret_tag()
    tag = f"  #{tag}" if tag else ""
    return [f"perf/replay_block_bytes_perevent,{b_ev:.0f},1.00{tag}",
            f"perf/replay_block_bytes_T={T},{b_blk:.0f},"
            f"{b_ev/b_blk:.2f}{tag}"]


def sweep_categories(n_instances: int = 28, n_items: int = 250,
                     policies=("cbd", "reduced_hybrid", "ppe_modified",
                               "la_binary"),
                     seeds=(0, 1, 2, 3, 4, 5)) -> List[str]:
    """Category-structured policies on the paper's noisy-prediction grid
    shape (instances x seeds): the host oracle loop (their only path before
    the unified replay engine) vs batched scan lanes.

    Three rows per grid: the host loop, the batched scan cold (wall clock
    including the per-policy compile, this suite's convention), and the
    batched scan warm (compile amortized - the steady state of extending a
    sweep, and the honest CPU proxy for the TPU lane-parallel win; derived
    column: speedup over the loop)."""
    from repro.core import run
    from repro.core.jaxsim import host_algorithm
    from repro.core.predictions import lognormal_predictions_batch
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, pad_predictions, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    preds = [lognormal_predictions_batch(i, 1.0, seeds) for i in insts]
    n_runs = n_instances * len(seeds) * len(policies)

    t0 = time.perf_counter()
    loop_usage = 0.0
    for p in policies:
        for inst, pr in zip(insts, preds):
            for s in range(len(seeds)):
                loop_usage += run(inst, host_algorithm(p),
                                  predicted_durations=pr[s]).usage_time
    t_loop = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = pack_instances(insts)
    pdeps = pad_predictions(batch, preds)
    batch_usage = 0.0
    for p in policies:
        batch_usage += float(run_batch(batch, p, pdeps, max_bins=64)
                             .usage_time.sum())
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in policies:
        run_batch(batch, p, pdeps, max_bins=64)
    t_warm = time.perf_counter() - t0

    tag = f"{n_instances}x{len(policies)}"
    return [f"perf/sweep_categories_loop_{tag},{t_loop/n_runs*1e6:.0f},"
            f"{loop_usage:.0f}",
            f"perf/sweep_categories_{tag},{t_cold/n_runs*1e6:.0f},"
            f"{batch_usage:.0f}",
            f"perf/sweep_categories_warm_{tag},{t_warm/n_runs*1e6:.0f},"
            f"{t_loop/t_warm:.2f}"]


def api_facade(n_instances: int = 28, n_items: int = 250,
               policies=("first_fit", "best_fit_l2", "greedy",
                         "nrt_prioritized")) -> List[str]:
    """The ``repro.api`` facade vs calling ``run_batch`` directly on the
    same pre-packed grid - both warm (compile + suite prep amortized), so
    the derived column is the pure facade overhead ratio (Experiment
    expansion, record building, ratio aggregation).  The acceptance bar
    is < 1.05 (5% overhead)."""
    from repro.api import Experiment, instances as api_instances
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    batch = pack_instances(insts)
    exp = Experiment(api_instances(insts, name="perf-facade"),
                     policies=policies)

    def direct():
        return sum(float(run_batch(batch, p, max_bins=64).usage_time.sum())
                   for p in policies)

    def facade():
        return exp.run().usage_total()

    u_direct, u_facade = direct(), facade()   # warm compiles + suite cache
    assert u_direct == u_facade, (u_direct, u_facade)
    # interleaved best-of-reps: host-load drift hits both paths alike and
    # min() discards contended reps, so the ratio isolates the facade cost
    td, tf = [], []
    for _ in range(3):
        td.append(obs.timeit(direct, n=1, warmup=0).best)
        tf.append(obs.timeit(facade, n=1, warmup=0).best)
    t_direct, t_facade = min(td), min(tf)
    n_runs = n_instances * len(policies)
    tag = f"{n_instances}x{len(policies)}"
    return [f"perf/api_facade_{tag},{t_facade/n_runs*1e6:.0f},"
            f"{t_facade/t_direct:.3f}"]


def sweep_batched_only(n_instances: int = 28, n_items: int = 250,
                       policies=("first_fit", "best_fit_l2", "greedy",
                                 "nrt_prioritized")) -> List[str]:
    """Just the batched side of ``sweep_grid`` (same row name, same grid):
    the regression-gate row for CI, where re-timing the slow per-instance
    loop baseline on every push would dominate the job."""
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    n_runs = n_instances * len(policies)
    t0 = time.perf_counter()
    batch = pack_instances(insts)
    usage = sum(float(run_batch(batch, p, max_bins=64).usage_time.sum())
                for p in policies)
    t_batch = time.perf_counter() - t0
    tag = f"{n_instances}x{len(policies)}"
    return [f"perf/sweep_batched_{tag},{t_batch/n_runs*1e6:.0f},"
            f"{usage:.0f}"]


def consolidate_sweep(n_instances: int = 28, n_items: int = 250,
                      policies=("first_fit", "best_fit_l2", "greedy",
                                "nrt_prioritized"),
                      thresholds=(0.15, 0.25, 0.5)) -> List[str]:
    """The consolidation axis on the CI-gate sweep grid.

    ``perf/consolidate_{tag}`` times the batched sweep with the default
    underload drain enabled (chunked replay + host planner interleave);
    derived column: total usage time, the consolidating twin of
    ``perf/sweep_batched_{tag}``'s derived column.

    ``perf/consolidate_frontier_t{thr}`` rows sketch the churn/usage
    frontier the paper family trades on: middle column = total migrations
    at that drain threshold, derived = usage relative to the
    non-consolidating baseline (< 1.0 means the drain paid for itself in
    usage time; migrations are the price).  Asserts consolidation never
    *increases* usage beyond rounding - the planner only executes
    whole-bin drains that close a bin."""
    from repro.consolidate import ConsolidationSpec
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    batch = pack_instances(insts)
    n_runs = n_instances * len(policies)
    base = sum(float(run_batch(batch, p, max_bins=64).usage_time.sum())
               for p in policies)

    spec = ConsolidationSpec.parse("underload:t0.25:e32")
    t0 = time.perf_counter()
    usage = sum(float(run_batch(batch, p, max_bins=64, consolidate=spec)
                      .usage_time.sum()) for p in policies)
    t_cons = time.perf_counter() - t0
    assert usage <= base * (1 + 1e-6), (usage, base)
    tag = f"{n_instances}x{len(policies)}"
    rows = [f"perf/consolidate_{tag},{t_cons/n_runs*1e6:.0f},{usage:.0f}"]
    for thr in thresholds:
        s = ConsolidationSpec.parse(f"underload:t{thr:g}:e32")
        migs, u = 0, 0.0
        for p in policies:
            r = run_batch(batch, p, max_bins=64, consolidate=s)
            migs += int(r.migrations.sum())
            u += float(r.usage_time.sum())
        rows.append(f"perf/consolidate_frontier_t{thr:g},{migs},"
                    f"{u / base:.4f}")
    return rows


def obs_overhead(n_instances: int = 28, n_items: int = 250,
                 policies=("first_fit", "best_fit_l2", "greedy",
                           "nrt_prioritized")) -> List[str]:
    """The obs layer's own cost on the CI-gate sweep (sweep_batched_28x4):

      * **disabled-mode overhead** - microbench the two disabled-mode
        primitives (a ``span()`` returning the shared no-op object, one
        ``counter_add`` dict upsert), count how many of each one warm sweep
        actually executes, and bound the instrumented-but-disabled cost as
        a fraction of the warm sweep wall clock.  Asserted < 2% (the
        tentpole budget); rides the row as the derived column.
      * **results invariance** - per-policy usage vectors must be
        bit-identical with spans enabled and with ``trace_level=1``
        (decision traces are extra scan *outputs*, never inputs).
    """
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    batch = pack_instances(insts)

    def sweep():
        return [np.asarray(run_batch(batch, p, max_bins=64).usage_time)
                for p in policies]

    u_warm = sweep()                               # warm compile
    # per-call cost of the disabled-mode primitives
    prev = obs.enabled()
    obs.enable(False)
    k = 100_000
    t0 = time.perf_counter()
    for _ in range(k):
        with obs.span("perf.calib"):
            pass
    t_span = (time.perf_counter() - t0) / k
    t0 = time.perf_counter()
    for _ in range(k):
        obs.counter_add("perf.calib")
    t_ctr = (time.perf_counter() - t0) / k
    obs.counter_add("perf.calib", -k)              # net the calibration out
    # how many instrumented call sites one warm sweep actually crosses
    # (delta-counted, so any ambient recording session keeps its events)
    with obs.recording(clear=False):
        ev0, c0 = len(obs.events()), obs.counter_ops()
        u_on = sweep()
        n_spans = len(obs.events()) - ev0
        n_ctrs = obs.counter_ops() - c0
    for a, b in zip(u_warm, u_on):
        assert (a == b).all(), "enabling spans must not change results"
    u_tr = [np.asarray(run_batch(batch, p, max_bins=64, trace_level=1)
                       .usage_time) for p in policies]
    for a, b in zip(u_warm, u_tr):
        assert (a == b).all(), "trace_level must not change decisions"
    st = obs.timeit(sweep, n=3, warmup=0)
    obs.enable(prev)
    frac = (n_spans * t_span + n_ctrs * t_ctr) / st.best
    assert frac < 0.02, \
        f"disabled-mode obs overhead {frac:.4f} exceeds the 2% budget " \
        f"({n_spans} spans @ {t_span*1e9:.0f}ns, " \
        f"{n_ctrs} counters @ {t_ctr*1e9:.0f}ns)"
    tag = f"{n_instances}x{len(policies)}"
    return [st.row(f"perf/obs_overhead_{tag}", f"{frac:.5f}")]


def resilience_overhead(n_instances: int = 28, n_items: int = 250,
                        policies=("first_fit", "best_fit_l2", "greedy",
                                  "nrt_prioritized")) -> List[str]:
    """The resilience layer's cost on the CI-gate sweep (sweep_batched_28x4):

      * **no-fault overhead** - microbench the two hot-path primitives the
        layer adds (a ``faults.fire`` seam crossing with no plan installed
        - two global reads - and one ``guard.run_ladder`` dispatch whose
        first rung succeeds), count how many of each one warm sweep
        actually executes, and bound the cost as a fraction of the warm
        sweep wall clock.  Asserted < 2% (the tentpole budget); rides the
        row as the derived column.
      * **results invariance** - per-policy usage vectors must be
        bit-identical with an (inert) fault plan installed: the harness
        only counts crossings until a spec arms.
    """
    from repro.data import make_azure_like_suite
    from repro.resilience import faults, guard
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    batch = pack_instances(insts)

    def sweep():
        return [np.asarray(run_batch(batch, p, max_bins=64).usage_time)
                for p in policies]

    u_warm = sweep()                               # warm compile
    # count the seam crossings one warm sweep executes: an inert plan (no
    # specs) counts every fire() without ever arming
    plan = faults.install(faults.FaultPlan([]))
    u_inert = sweep()
    n_fire = sum(plan.calls.values())
    n_ladders = plan.calls.get("sweep.scan", 0)    # one run_ladder each
    faults.clear()
    for a, b in zip(u_warm, u_inert):
        assert (a == b).all(), \
            "an inert fault plan must not change results"
    # per-call cost of the no-fault primitives
    k = 100_000
    t0 = time.perf_counter()
    for _ in range(k):
        faults.fire("perf.calib")
    t_fire = (time.perf_counter() - t0) / k
    rungs = guard.replay_rungs("jnp", 0, 1)
    t0 = time.perf_counter()
    for _ in range(k):
        guard.run_ladder(lambda r: 0, rungs, site="perf.calib")
    t_ladder = (time.perf_counter() - t0) / k
    st = obs.timeit(sweep, n=3, warmup=0)
    frac = (n_fire * t_fire + n_ladders * t_ladder) / st.best
    assert frac < 0.02, \
        f"no-fault resilience overhead {frac:.4f} exceeds the 2% budget " \
        f"({n_fire} seams @ {t_fire*1e9:.0f}ns, " \
        f"{n_ladders} ladders @ {t_ladder*1e9:.0f}ns)"
    tag = f"{n_instances}x{len(policies)}"
    return [st.row(f"perf/resilience_overhead_{tag}", f"{frac:.5f}")]


def sweep_retrace(n_items: int = 30, d: int = 3) -> List[str]:
    """The PR-5 one-trace-per-geometry fix as a monitored perf invariant:
    after warming a 6-instance x 2-prediction-row grid, running the same
    padded geometry as 12 x 1 lanes (and the 6 x 2 cell again) must be a
    pure jit-cache hit.  Middle column: warm wall clock for the two grids;
    derived column: the ``sweep.jit_trace`` counter delta - CI gates on 0
    (``benchmarks/run.py --check``)."""
    from repro.sweep import pack_instances, pad_predictions, run_batch
    i6 = [quantized_instance(40 + k) for k in range(6)]
    i12 = [quantized_instance(60 + k) for k in range(12)]
    b6 = pack_instances(i6)
    p6 = pad_predictions(
        b6, [np.stack([i.durations, 2.0 * i.durations]) for i in i6])
    b12 = pack_instances(i12)
    run_batch(b6, "greedy", p6, max_bins=64)       # warm: one trace
    before = obs.counter_get("sweep.jit_trace")
    st = obs.timeit(lambda: (run_batch(b12, "greedy", max_bins=64),
                             run_batch(b6, "greedy", p6, max_bins=64)),
                    n=3, warmup=0)
    retraces = obs.counter_get("sweep.jit_trace") - before
    return [st.row("perf/sweep_retrace_6x2v12x1", f"{retraces:.0f}")]


def quantized_instance(seed: int, n: int = 30, d: int = 3):
    """A single fp32-exact instance (1/64-grid sizes, integer times) - the
    same shape family the blocked-replay parity tests use."""
    from repro.core import Instance
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


_SHARDED_BENCH = """
import time
import jax, numpy as np
from repro.data import make_azure_like_suite
from repro.sweep import pack_instances, run_batch
insts = make_azure_like_suite(n_instances=28, n_items=250, seed=11)
batch = pack_instances(insts)
policies = ("first_fit", "best_fit_l2", "greedy", "nrt_prioritized")
for shard in ("never", "always"):
    t0 = time.perf_counter()
    usage = sum(float(run_batch(batch, p, max_bins=64, shard=shard)
                      .usage_time.sum()) for p in policies)
    print(f"{shard},{time.perf_counter() - t0},{usage}")
"""


def sweep_sharded(ndev: int = 4) -> List[str]:
    """The 28x4 sweep grid with the lane axis sharded over ``ndev`` forced
    host devices vs the single-device path, in a subprocess (device count is
    fixed at jax init).  A host-device emulation row: the child runs with
    ``JAX_PLATFORMS=cpu``, so it never contends with this process for a
    chip, and on one physical CPU the shards share cores."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_BENCH], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-500:])
    times, usages = {}, {}
    for line in proc.stdout.strip().splitlines():
        shard, t, usage = line.split(",")
        times[shard] = float(t)
        usages[shard] = float(usage)
    assert usages["never"] == usages["always"], \
        f"sharded results diverged: {usages}"
    n_runs = 28 * 4
    return [f"perf/sweep_sharded_28x4,{times['always']/n_runs*1e6:.0f},"
            f"{times['never']/times['always']:.2f}"]


def jaxsim_vs_oracle() -> List[str]:
    from repro.core import get_algorithm, run
    from repro.core.jaxsim import simulate
    from repro.data import make_azure_like_suite
    inst = make_azure_like_suite(n_instances=1, n_items=2000)[0]
    t0 = time.perf_counter()
    r = run(inst, get_algorithm("first_fit"))
    t_or = time.perf_counter() - t0
    simulate(inst, "first_fit", max_bins=r.peak_open_bins + 8)   # compile
    t0 = time.perf_counter()
    j = simulate(inst, "first_fit", max_bins=r.peak_open_bins + 8)
    t_jx = time.perf_counter() - t0
    rows = [f"perf/oracle_engine_2k_items,{t_or*1e6:.0f},{r.usage_time:.0f}",
            f"perf/jaxsim_2k_items,{t_jx*1e6:.0f},{j.usage_time:.0f}"]
    return rows


def sweep_grid(n_instances: int = 28, n_items: int = 250,
               policies=("first_fit", "best_fit_l2", "greedy",
                         "nrt_prioritized")) -> List[str]:
    """Batched sweep runner vs the per-instance simulate() loop on an
    n_instances x len(policies) grid.  The loop path re-traces per instance
    (every instance has its own event-tensor shape); the batched path
    compiles once per policy.  Wall clock includes compilation for both -
    that is the real cost of evaluating a fresh grid."""
    from repro.core.jaxsim import simulate
    from repro.data import make_azure_like_suite
    from repro.sweep import pack_instances, run_batch
    insts = make_azure_like_suite(n_instances=n_instances, n_items=n_items,
                                  seed=11)
    grid = n_runs = n_instances * len(policies)

    t0 = time.perf_counter()
    loop_usage = 0.0
    for p in policies:
        for inst in insts:
            loop_usage += simulate(inst, p, max_bins=64).usage_time
    t_loop = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = pack_instances(insts)
    batch_usage = 0.0
    for p in policies:
        batch_usage += float(run_batch(batch, p, max_bins=64)
                             .usage_time.sum())
    t_batch = time.perf_counter() - t0

    tag = f"{n_instances}x{len(policies)}"
    return [f"perf/sweep_loop_{tag},{t_loop/n_runs*1e6:.0f},{loop_usage:.0f}",
            f"perf/sweep_batched_{tag},{t_batch/n_runs*1e6:.0f},"
            f"{batch_usage:.0f}",
            f"perf/sweep_speedup_{tag},{t_batch*1e6:.0f},"
            f"{t_loop/t_batch:.2f}"]


def serving_fleet() -> List[str]:
    from repro.serving.fleet import attach_predictions, simulate_fleet, \
        synth_requests
    reqs = attach_predictions(synth_requests(2000), sigma=0.5)
    rows = []
    for pol in ["round_robin", "first_fit", "greedy", "nrt_prioritized"]:
        t0 = time.perf_counter()
        r = simulate_fleet(reqs, pol)
        rows.append(f"perf/fleet_{pol},{(time.perf_counter()-t0)*1e6:.0f},"
                    f"{r['replica_seconds']:.0f}")
    return rows


def serve_throughput(n: int = 2000, rate: float = 5e4, tps: float = 1.2e5,
                     geometries=(1, 8, 32, 256)) -> List[str]:
    """Batched admission throughput: one Poisson trace at control-plane
    rate served through the double-buffered block dispatcher at
    T = 1 / 32 / 256.  Middle column: us per placed request (best of 3
    warm passes); derived: requests placed per second.  The three runs
    are asserted decision-for-decision equal to each other AND to the
    sequential host oracle before any row is emitted - a batching config
    that changed placements would fail the bench, not ship a number.
    Extra rows: p50/p99 admission-to-placement latency at T=256 and the
    demand-vector memo hit rate (counter-verified)."""
    import heapq

    from repro.serving.dispatch import serve_traffic
    from repro.serving.scheduler import DVBPScheduler, ReplicaCapacity
    from repro.serving.traffic import poisson_requests

    caps = ReplicaCapacity()
    reqs = poisson_requests(n, rate=rate, seed=0, sigma_pred=0.3)

    sched = DVBPScheduler("best_fit", caps, {"norm": "linf"},
                          tokens_per_second=tps)
    heap, oracle = [], {}
    for r in sorted(reqs, key=lambda x: x.arrival):
        while heap and heap[0][0] <= r.arrival:
            ft, rid = heapq.heappop(heap)
            sched.finish(rid, ft)
        oracle[r.rid] = sched.place(r, r.arrival)
        heapq.heappush(heap, (r.arrival + r.decode_len / tps, r.rid))

    memo0 = {k: obs.counter_get(k) for k in
             ("serving.size_memo_hit", "serving.size_memo_miss")}
    rows, reports = [], {}
    for T in (1, 32, 256):
        kw = dict(tps=tps, batch_max=T, geometries=geometries,
                  max_bins=64)
        serve_traffic(reqs, "best_fit_linf", caps, **kw)     # warm traces
        best = None
        for _ in range(3):
            rep = serve_traffic(reqs, "best_fit_linf", caps, **kw)
            assert rep.placements == oracle, \
                f"T={T} diverged from the sequential oracle"
            if best is None or rep.wall_seconds < best.wall_seconds:
                best = rep
        reports[T] = best
        rows.append(f"perf/serve_throughput_T={T},"
                    f"{best.wall_seconds / best.placed * 1e6:.1f},"
                    f"{best.throughput:.0f}")
    p50, p99 = reports[256].latency_quantiles()
    rows.append(f"perf/serve_latency_p50_T=256,{p50 * 1e6:.1f},1.00")
    rows.append(f"perf/serve_latency_p99_T=256,{p99 * 1e6:.1f},1.00")
    hits = obs.counter_get("serving.size_memo_hit") \
        - memo0["serving.size_memo_hit"]
    miss = obs.counter_get("serving.size_memo_miss") \
        - memo0["serving.size_memo_miss"]
    rate_ = hits / (hits + miss) if hits + miss else 0.0
    rows.append(f"perf/serve_demand_memo,{hits + miss:.0f},{rate_:.2f}")
    return rows


def serve_retrace(n: int = 300, geometries=(1, 8, 32)) -> List[str]:
    """The serving analogue of ``perf/sweep_retrace_6x2v12x1``: padding
    every admission batch to a fixed geometry set bounds the dispatch jit
    trace count.  After one warm pass, a second identical pass must add
    ZERO ``serving.jit_trace`` - CI gates the derived column at 0."""
    from repro.serving.dispatch import serve_traffic
    from repro.serving.scheduler import ReplicaCapacity
    from repro.serving.traffic import poisson_requests

    caps = ReplicaCapacity()
    reqs = poisson_requests(n, rate=5e4, seed=0, sigma_pred=0.3)
    kw = dict(tps=1.2e5, batch_max=geometries[-1], geometries=geometries,
              max_bins=64)
    serve_traffic(reqs, "best_fit_linf", caps, **kw)         # warm
    before = obs.counter_get("serving.jit_trace")
    st = obs.timeit(
        lambda: serve_traffic(reqs, "best_fit_linf", caps, **kw),
        n=3, warmup=0)
    retraces = obs.counter_get("serving.jit_trace") - before
    return [st.row("perf/serve_retrace", f"{retraces:.0f}")]


def _interpret_tag() -> str:
    """Rows timed through Pallas *interpret-mode emulation* on CPU carry a
    structured ``mode=interpret`` comment token: ``benchmarks/run.py``
    parses it into the bench JSON and CI excludes tagged rows from
    speedup-style comparisons (emulation timings measure the emulator,
    not the kernel)."""
    return "" if jax.default_backend() == "tpu" else " mode=interpret"


def stream_replay(n_items: int = 10_000, big_items: int = 100_000,
                  chunk_events: int = 2048,
                  item_rows: int = 2048) -> List[str]:
    """The streamed chunked replay (``repro.stream``) headline rows: a
    full synthetic azure-like lane replayed in fixed-geometry chunks over
    a recycled item-row pool, bit-equality-gated against the in-memory
    ``simulate`` before any number is emitted.

    ``perf/stream_replay_10k`` / ``_100k`` - us per event (middle column)
    and the *accounted device-side peak* in MB (derived column: carry +
    pool + staged chunks, the O(max-alive) memory-model claim - at 100k
    items the in-memory event tensor alone would be ~100x larger).
    ``perf/stream_prefetch_10k`` - the same replay with ``prefetch=0``
    (fence after every chunk); derived column: sync/prefetched wall-clock
    ratio.  On a CPU-only host the device shares cores with the staging
    thread, so the ratio sits ~1.0 there; the overlap pays on real
    accelerators (same caveat family as the ``mode=interpret`` tags)."""
    from repro.core.jaxsim import simulate
    from repro.stream import replay_stream, synthetic_source

    rows = []
    kw = dict(chunk_events=chunk_events, item_rows=item_rows, max_bins=128)
    src = synthetic_source(n_items, seed=21)
    ref = simulate(src.inst, "first_fit", max_bins=128)
    res = replay_stream(src, "first_fit", **kw)          # warm + gate
    assert res.usage == float(ref.usage_time), "stream/simulate diverged"
    assert res.opened == int(ref.n_bins_opened)
    E = 2 * n_items
    st = obs.timeit(lambda: replay_stream(src, "first_fit", **kw),
                    n=3, warmup=0)
    rows.append(st.row(f"perf/stream_replay_{n_items // 1000}k",
                       f"{res.peak_device_bytes / 1e6:.2f}", scale=1 / E))
    st_sync = obs.timeit(
        lambda: replay_stream(src, "first_fit", prefetch=0, **kw),
        n=3, warmup=0)
    rows.append(st.row(f"perf/stream_prefetch_{n_items // 1000}k",
                       f"{st_sync.best / st.best:.2f}", scale=1 / E))

    big = synthetic_source(big_items, seed=22)
    kw_big = dict(chunk_events=chunk_events, item_rows=item_rows,
                  max_bins=256)
    Eb = 2 * big_items
    st_big = obs.timeit(lambda: replay_stream(big, "first_fit", **kw_big),
                        n=1, warmup=1)
    res_big = replay_stream(big, "first_fit", **kw_big)
    ref_big = simulate(big.inst, "first_fit", max_bins=res_big.max_bins)
    assert res_big.usage == float(ref_big.usage_time), \
        "stream/simulate diverged at full-trace scale"
    assert res_big.item_rows < big_items, "pool not bounded"
    rows.append(st_big.row(f"perf/stream_replay_{big_items // 1000}k",
                           f"{res_big.peak_device_bytes / 1e6:.2f}",
                           scale=1 / Eb))
    return rows


def stream_replay_fast(n_items: int = 3000) -> List[str]:
    """The CI smoke lane: ``perf/stream_replay_6k`` (6k events), gated on
    (1) bit-equality with ``simulate`` including placements, (2) the
    accounted device-side peak staying O(pool) - a ceiling far under the
    materialized event tensor, and (3) a process peak-RSS ceiling (a
    streamed replay that silently materialized the trace would blow both).
    Middle column: us per event; derived: accounted peak MB."""
    import resource

    from repro.core.jaxsim import simulate
    from repro.stream import InstanceSource, replay_stream, \
        synthetic_source

    src = synthetic_source(n_items, seed=17)
    kw = dict(chunk_events=1024, item_rows=256, max_bins=128)
    ref = simulate(src.inst, "first_fit", max_bins=128)
    res = replay_stream(InstanceSource(src.inst), "first_fit",
                        collect_placements=True, **kw)
    assert res.usage == float(ref.usage_time), "stream/simulate diverged"
    assert res.opened == int(ref.n_bins_opened)
    assert (res.placements == np.asarray(ref.placements)).all()
    assert res.item_rows < n_items, "pool not bounded"
    assert res.peak_device_bytes < 32 * 1e6, \
        f"accounted peak {res.peak_device_bytes} exceeds the 32MB ceiling"
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    assert rss_gb < 12.0, f"peak RSS {rss_gb:.1f}GB exceeds the ceiling"
    E = 2 * n_items
    # warmup compiles the harvest-free chunk step (the gate run above
    # traced the placement-harvesting variant)
    st = obs.timeit(lambda: replay_stream(src, "first_fit", **kw),
                    n=3, warmup=1)
    return [st.row("perf/stream_replay_6k",
                   f"{res.peak_device_bytes / 1e6:.2f}", scale=1 / E)]


def roofline_summary() -> List[str]:
    rows = []
    for path in sorted(glob.glob("experiments/dryrun/*_16x16.json")):
        with open(path) as f:
            rec = json.load(f)
        r = rec["roofline"]
        dom_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        frac = r["compute_s"] / dom_s if dom_s else 0.0
        rows.append(f"roofline/{rec['arch']}/{rec['shape']},"
                    f"{dom_s*1e6:.0f},{frac:.3f}  "
                    f"# dominant={r['dominant']} useful={r['useful_ratio']:.2f}")
    return rows
