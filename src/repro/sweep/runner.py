"""Batched DVBP replay: one fused lane-batched scan per (grid, policy).

``run_batch`` evaluates every lane of an ``InstanceBatch`` (and every
prediction-seed row) in a single device computation - the per-instance
``jaxsim.simulate`` loop re-traces and re-dispatches once per (instance,
policy) pair because every instance has its own event-tensor shape; here
the padded batch compiles once per flattened padded geometry
(L = B*S lanes, n_max, d, max_bins -> Np, policy, backend, block_events)
and the scan runs all lanes in lockstep.  The (B, S) -> lane flattening
happens *outside* the jit, so grids that vary which instances or how many
seed rows fill the lanes - but not the padded geometry - share one trace.

Every policy in ``jaxsim.SCAN_POLICIES`` is a lane: the score-based Any Fit
family AND the category-structured families (CBD/CBDT, Hybrid variants,
RCP/PPE, Lifetime Alignment, adaptive) - ``core.jaxsim._replay_batch`` is
the single replay engine, extended with carried category state.  The (B, S)
grid always flattens to one lane axis (lane = b*S + s) and replays as a
*single* scan over the event index.

Backends (``jaxsim.BACKENDS``): with ``backend="jnp"`` the per-step
placement decision is the inline vmapped select on a compact carry; with
"pallas"/"pallas_interpret" it is the fused ``kernels.fitscore`` kernel
with the scan carry held in the kernel's padded layout - zero host round
trips per step.  "auto" resolves to the kernel on TPU, jnp elsewhere.
``block_events=T > 1`` (kernel backends) goes one rung further: the
event-blocked replay megakernel processes whole T-event blocks on-chip
with the carry resident in VMEM, written back to HBM once per block (see
``kernels.fitscore.fitscore_replay_block`` and sweep/README.md).  All
paths are bit-identical on fp32-exact instances (tests/test_sweep.py,
tests/test_sweep_categories.py, tests/test_replay_block.py).

Sharding: when more than one local device is visible, the lane axis is
sharded across them via ``jax.shard_map`` (lanes padded to a device
multiple; each device replays its lane shard independently - the replay has
no cross-lane communication, so the map is embarrassingly parallel).  With
one device the plain single-device path runs, unchanged.

Overflow handling mirrors ``simulate(auto_grow=True)`` but lane-wise: after
a batched run, any lane whose slot pool overflowed (in any seed row) is
gathered into a sub-batch and re-run with ``max_bins`` doubled, repeatedly,
instead of returning garbage for those lanes.  The ladder composes with
sharding (each rung re-pads and re-shards the surviving lanes).  Each rung
costs a re-compile for the (smaller) sub-batch shape; starting ``max_bins``
near the expected peak open-bin count avoids the ladder entirely.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..consolidate import ConsolidationSpec, consolidated_replay
from ..core.jaxsim import (MAX_BINS_CAP, _replay_batch, grow_max_bins,
                           known_policy, replay_category_bytes,
                           replay_loads_shape, replay_scan_steps,
                           resolve_backend)
from ..obs.trace import ReplayTrace, from_scan
from ..resilience import faults, guard
from ..resilience.checkpoint import ReplayCheckpointer, checkpointed_replay
from .batching import InstanceBatch, instances_pdeps


def _flatten_lanes(sizes, times, kinds, items, pdeps, dmask, arrivals,
                   rdeps, n_items):
    """Flatten the (B, S) grid to L = B*S lanes, lane = b*S + s: per-lane
    arrays repeat b-major to match ``pdeps.reshape``'s row order (the single
    source of the lane ordering for both the kernel and sharded paths)."""
    B, S, n_max = pdeps.shape
    rep = (lambda a: jnp.repeat(a, S, axis=0)) if S > 1 else (lambda a: a)
    return (rep(sizes), rep(times), rep(kinds), rep(items),
            pdeps.reshape(B * S, n_max), rep(dmask), rep(arrivals),
            rep(rdeps), rep(n_items))


def lane_device_count() -> int:
    """Local devices available to shard the lane axis over."""
    return jax.local_device_count()


def _simulate_lanes_impl(sizes, times, kinds, items, pdeps, dmask, arrivals,
                         rdeps, n_items, *, policy: str, max_bins: int,
                         backend: str, block_events: int = 0,
                         trace_level: int = 0):
    """Flattened-lane replay: ``pdeps`` is (L, n_max) - exactly one
    prediction row per lane.  This is the shard_map body: a single
    lane-batched scan."""
    res = _replay_batch(
        sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps, n_items,
        policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, trace_level=trace_level)
    usage, opened, _placements, overflow = res[:4]
    if trace_level:
        return usage, opened, overflow, res[4]
    return usage, opened, overflow


# THE jitted replay.  Keyed on the *flattened padded geometry* only -
# (L, n_max, d) input shapes plus the static (policy, max_bins -> Np,
# backend, block_events -> T) - so a grid sweep that varies which
# instances / how many seed rows fill the lanes (but not the padded
# geometry) compiles exactly once per policy
# (tests/test_replay_block.py::test_one_trace_across_grid).  The (B, S) ->
# lane flattening happens OUTSIDE the jit: jitting at (B, S) granularity
# used to retrace a 6x2 grid and a 12x1 grid separately even though they
# run the identical flattened computation.
_simulate_lanes = jax.jit(_simulate_lanes_impl,
                          static_argnames=("policy", "max_bins", "backend",
                                           "block_events", "trace_level"))


def _jit_cache_entries() -> int:
    """Total compiled-trace count across the jitted replay entry points -
    the source of the ``sweep.jit_trace`` counter (the PR-5 "one trace per
    geometry" fix as a monitored invariant, not just a regression test)."""
    return int(_simulate_lanes._cache_size() +
               _simulate_batch_sharded._cache_size())


def _simulate_batch(sizes, times, kinds, items, pdeps, dmask, arrivals,
                    rdeps, n_items, *, policy: str, max_bins: int,
                    backend: str = "jnp", block_events: int = 0,
                    trace_level: int = 0):
    """pdeps: (B, S, n_max); everything else (B, ...).  Returns
    (usage (B,S), opened (B,S), overflow (B,S), trace) - placements are
    dead-code eliminated to keep device->host transfers small.  ``trace``
    is None unless ``trace_level >= 1``, else the per-event series dict
    with flat-lane leading axes (L = B*S, E, ...)."""
    B, S, _ = pdeps.shape
    out = _simulate_lanes(
        *_flatten_lanes(sizes, times, kinds, items, pdeps, dmask, arrivals,
                        rdeps, n_items),
        policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, trace_level=trace_level)
    usage, opened, overflow = out[:3]
    return (usage.reshape(B, S), opened.reshape(B, S),
            overflow.reshape(B, S), out[3] if trace_level else None)


@partial(jax.jit, static_argnames=("policy", "max_bins", "backend", "ndev",
                                   "block_events"))
def _simulate_batch_sharded(sizes, times, kinds, items, pdeps, dmask,
                            arrivals, rdeps, n_items, *, policy: str,
                            max_bins: int, backend: str, ndev: int,
                            block_events: int = 0):
    """Shard the flattened lane axis over ``ndev`` local devices.  L must
    be a multiple of ndev (``_run_arrays`` pads); each shard replays its
    lanes with the plain single-device computation - no collectives."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    mesh = Mesh(np.asarray(jax.local_devices()[:ndev]), ("lanes",))
    f = shard_map(
        partial(_simulate_lanes_impl, policy=policy, max_bins=max_bins,
                backend=backend, block_events=block_events),
        mesh=mesh, in_specs=P("lanes"), out_specs=P("lanes"),
        check_vma=False)
    return f(sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps,
             n_items)


def _run_arrays(arrays, *, policy: str, max_bins: int, backend: str,
                ndev: int, block_events: int = 0, trace_level: int = 0):
    """One batched run, sharded over lanes when ndev > 1.

    The sharded path flattens the (B, S) grid to L = B*S lanes (so seed
    rows balance across devices too), pads L to a device multiple by
    replicating existing lanes - wrapping around when fewer than ``pad``
    lanes exist - and drops the padding rows on the way out.  Trace-level
    replay forces the single-device path: the stacked (L, E, ...) trace
    outputs don't earn a re-shard and traces are a debugging/figure mode,
    not a throughput mode."""
    faults.fire("sweep.scan")
    if ndev <= 1 or trace_level:
        return _simulate_batch(*arrays, policy=policy, max_bins=max_bins,
                               backend=backend, block_events=block_events,
                               trace_level=trace_level)
    B, S, _ = arrays[4].shape
    flat = _flatten_lanes(*arrays)
    L = B * S
    pad = (-L) % ndev
    if pad:
        # wrap-around replication: tile whole copies of the lane axis up
        # to the padded length, then slice - exact even when the device
        # count dwarfs the lane count (pad > L needs ceil(total/L) > 2
        # copies; tests/test_stream.py pins ndev > 2L)
        total = L + pad
        reps = -(-total // L)
        flat = tuple(jnp.concatenate([a] * reps, axis=0)[:total]
                     for a in flat)
    u, o, ov = _simulate_batch_sharded(*flat, policy=policy,
                                       max_bins=max_bins, backend=backend,
                                       ndev=ndev, block_events=block_events)
    return (u[:L].reshape(B, S), o[:L].reshape(B, S),
            ov[:L].reshape(B, S), None)


def _dispatch(arrays, *, policy: str, max_bins: int, backend: str,
              ndev: int, block_events: int = 0, trace_level: int = 0):
    """One batched run behind the resilience ladder: transient device
    failures retry with backoff, persistent ones degrade blocked ->
    per-event -> jnp / sharded -> single-device (``guard.replay_rungs``).
    Every rung replays identical decisions, so the results of a degraded
    dispatch are bit-identical to the requested plan - just slower.  Blocks
    on the device results so execution-time failures surface inside the
    ladder, not at the caller's first ``np.asarray``."""
    rungs = guard.replay_rungs(backend, block_events, ndev)

    def attempt(rung):
        out = _run_arrays(arrays, policy=policy, max_bins=max_bins,
                          backend=rung.backend, ndev=rung.ndev,
                          block_events=rung.block_events,
                          trace_level=trace_level)
        jax.block_until_ready(out[:3])
        return out

    rung, out = guard.run_ladder(attempt, rungs, site="sweep.scan")
    if rung is not rungs[0]:
        obs.annotate(degraded_to=rung.label)
    return out


def _run_checkpointed(arrays, *, policy: str, max_bins: int, backend: str,
                      block_events: int, ckpt: ReplayCheckpointer,
                      key: str):
    """One batched run through the segmented checkpointed replay (single
    device by construction; ``resilience.checkpoint``).  Same outputs as
    ``_run_arrays`` minus traces."""
    faults.fire("sweep.scan")
    B, S, _ = arrays[4].shape
    flat = _flatten_lanes(*arrays)
    u, o, _placements, ov = checkpointed_replay(
        flat, policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, ckpt=ckpt, key=key)
    return (np.asarray(u).reshape(B, S), np.asarray(o).reshape(B, S),
            np.asarray(ov).reshape(B, S), None)


def _run_consolidated(arrays, *, policy: str, max_bins: int, backend: str,
                      block_events: int, spec: ConsolidationSpec):
    """One batched run through the consolidating chunked driver
    (``consolidate.consolidated_replay``; single device, no traces - the
    planner needs the carry on the host between chunks anyway).  Returns
    the ``_run_arrays`` triple plus the per-cell churn arrays."""
    faults.fire("sweep.scan")
    B, S, _ = arrays[4].shape
    flat = _flatten_lanes(*arrays)
    u, o, _placements, ov, stats = consolidated_replay(
        *flat, policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, spec=spec)
    churn = {"migrations":
             np.asarray(stats["migrations"]).reshape(B, S),
             "migration_cost":
             np.asarray(stats["migration_cost"]).reshape(B, S)}
    return (np.asarray(u).reshape(B, S), np.asarray(o).reshape(B, S),
            np.asarray(ov).reshape(B, S), churn)


@dataclasses.dataclass
class BatchRunResult:
    usage_time: np.ndarray     # (B, S) float
    n_bins_opened: np.ndarray  # (B, S) int
    overflowed: np.ndarray     # (B, S) bool (True only if the cap was hit)
    max_bins: np.ndarray       # (B,) slot-pool size that produced each lane
    trace: Optional[ReplayTrace] = None  # trace_level >= 1 only
    migrations: Optional[np.ndarray] = None      # (B, S), consolidate only
    migration_cost: Optional[np.ndarray] = None  # (B, S), consolidate only

    @property
    def S(self) -> int:
        return self.usage_time.shape[1]


def run_batch(batch: InstanceBatch, policy: str,
              pdeps: Optional[np.ndarray] = None, max_bins: int = 64,
              max_bins_cap: int = MAX_BINS_CAP,
              auto_grow: bool = True, backend: Optional[str] = None,
              shard: str = "auto", block_events: int = 0,
              trace_level: int = 0,
              checkpoint: Optional[ReplayCheckpointer] = None,
              checkpoint_key: str = "",
              consolidate: Optional[ConsolidationSpec] = None
              ) -> BatchRunResult:
    """Replay every lane of ``batch`` under ``policy`` (any
    ``jaxsim.SCAN_POLICIES`` name, category-structured policies included).

    ``pdeps``: (B, S, n_max) predicted departure times (see
    ``batching.pad_predictions``); defaults to the real departures
    (clairvoyant / non-clairvoyant replay).

    ``backend``: scoring engine (``jaxsim.BACKENDS``; None == "auto" ==
    Pallas kernel on TPU, inline jnp elsewhere).  ``shard``: "auto" shards
    the lane axis over all local devices when more than one is visible;
    "never" forces the single-device path; "always" asserts multi-device.
    ``block_events`` > 1 (kernel backends only) runs the event-blocked
    replay megakernel: blocks of that many events per invocation with the
    carry resident on-chip.  All three are execution arguments - they
    never change the replayed decisions.

    ``trace_level`` >= 1 also returns the per-event decision series as
    ``result.trace`` (an ``obs.ReplayTrace``; level >= 2 adds the per-slot
    alive mask).  Tracing never changes decisions, but it does change the
    execution plan: per-event replay (the blocked megakernel is bypassed)
    on a single device.  ``trace_level=0`` runs exactly today's code path.

    ``checkpoint`` (a ``resilience.ReplayCheckpointer``) replays in
    checkpointed segments so a killed run resumes bit-identically
    (single-device, no traces); ``checkpoint_key`` names the snapshot
    file.  Without it, dispatch runs behind the resilience ladder
    (``_dispatch``): transient device failures retry, persistent ones
    degrade blocked -> per-event -> jnp / sharded -> single-device with
    identical results.

    ``consolidate`` (an enabled ``ConsolidationSpec``) routes the replay
    through the chunked consolidating driver: scan chunks alternate with
    host planning and MIGRATE chunks (``consolidate.consolidated_replay``)
    and the result gains per-cell ``migrations`` / ``migration_cost``
    arrays.  The consolidating path is single-device and untraced and
    bypasses checkpointing; ``None`` (or a disabled spec is rejected by
    the driver) runs exactly the paths above, bit-identically to a build
    without the consolidation axis.
    """
    assert known_policy(policy), f"{policy!r} is not a scan policy"
    assert shard in ("auto", "never", "always"), shard
    backend = resolve_backend(backend)
    if pdeps is None:
        pdeps = instances_pdeps(batch)
    B, S, _ = pdeps.shape
    assert B == batch.B
    ndev = 1 if shard == "never" else lane_device_count()
    if shard == "always":
        assert ndev > 1, "shard='always' requires multiple local devices"

    usage = np.zeros((B, S))
    opened = np.zeros((B, S), np.int64)
    over = np.ones((B, S), bool)
    mb_used = np.full(B, max_bins, np.int64)
    migrations = migration_cost = None
    if consolidate is not None:
        assert consolidate.enabled, \
            "pass consolidate=None for non-consolidating runs"
        migrations = np.zeros((B, S), np.int64)
        migration_cost = np.zeros((B, S))
    lanes = np.arange(B)
    mb = max_bins
    arrays = (batch.sizes, batch.times, batch.kinds, batch.items, pdeps,
              batch.dmask, batch.arrivals, batch.pdeps, batch.n_items)
    trace_np = None
    events = 0
    with obs.span("sweep.run_batch", policy=policy, backend=backend,
                  B=B, S=S) as rb_span:
        # which loads layout and category state the first scan carries,
        # at what size
        _, n_max, d = batch.sizes.shape
        blocked = 0 if trace_level else block_events
        rb_span.set(loads_bytes=4 * math.prod(replay_loads_shape(
            B * S, max_bins, d, backend=backend, block_events=blocked)),
            category_bytes=replay_category_bytes(
                policy, max_bins, d, n_max, L=B * S, backend=backend,
                block_events=blocked))
        rungs = 0
        while True:
            events += 2 * int(batch.n_items[lanes].sum(dtype=np.int64)) * S
            with obs.span("sweep.flatten"):
                sub = tuple(jnp.asarray(a[lanes]) for a in arrays)
            obs.counter_add("sweep.device_transfer_bytes",
                            sum(int(x.nbytes) for x in sub))
            c0 = _jit_cache_entries()
            with obs.span("sweep.scan", policy=policy, max_bins=mb,
                          lanes=int(lanes.size) * S,
                          steps=replay_scan_steps(
                              batch.times.shape[1], backend=backend,
                              block_events=block_events,
                              trace_level=trace_level)) as sc:
                if consolidate is not None:
                    u, o, ov, churn = _run_consolidated(
                        sub, policy=policy, max_bins=mb, backend=backend,
                        block_events=block_events, spec=consolidate)
                    tr = None
                    migrations[lanes] = churn["migrations"]
                    migration_cost[lanes] = churn["migration_cost"]
                elif checkpoint is not None and not trace_level:
                    u, o, ov, tr = _run_checkpointed(
                        sub, policy=policy, max_bins=mb, backend=backend,
                        block_events=block_events, ckpt=checkpoint,
                        key=f"{checkpoint_key or policy}-mb{mb}")
                else:
                    u, o, ov, tr = _dispatch(sub, policy=policy,
                                             max_bins=mb, backend=backend,
                                             ndev=ndev,
                                             block_events=block_events,
                                             trace_level=trace_level)
                usage[lanes] = np.asarray(u)   # blocks on device results
                opened[lanes] = np.asarray(o)
                over[lanes] = np.asarray(ov)
            retraced = _jit_cache_entries() - c0
            if retraced:
                obs.counter_add("sweep.jit_trace", retraced)
                sc.set(retraced=retraced)
            else:
                obs.counter_add("sweep.jit_cache_hit")
            obs.counter_add("sweep.scan_calls")
            mb_used[lanes] = mb
            if tr is not None:
                tr = {k: np.asarray(v) for k, v in tr.items()}
                if trace_np is None:
                    trace_np = {k: np.zeros((B * S,) + v.shape[1:],
                                            v.dtype)
                                for k, v in tr.items()}
                rows = (lanes[:, None] * S + np.arange(S)).ravel()
                for k, v in tr.items():
                    trace_np[k][rows] = v
            lanes = lanes[np.asarray(ov).any(axis=1)]
            if lanes.size == 0 or not auto_grow or mb >= max_bins_cap:
                break
            mb = grow_max_bins(mb, max_bins_cap)
            rungs += 1
            obs.counter_add("sweep.overflow_rungs")
        rb_span.set(events=events)
        if rungs:
            rb_span.set(overflow_rungs=rungs)
    trace = None if trace_np is None else from_scan(
        trace_np, batch.times, batch.kinds, batch.items, policy=policy,
        S=S)
    return BatchRunResult(usage, opened, over, mb_used, trace,
                          migrations=migrations,
                          migration_cost=migration_cost)


def run_grid(batch: InstanceBatch, policies: Sequence[str],
             pdeps: Optional[np.ndarray] = None, max_bins: int = 64,
             max_bins_cap: int = MAX_BINS_CAP,
             backend: Optional[str] = None, shard: str = "auto",
             block_events: int = 0,
             trace_level: int = 0) -> Dict[str, BatchRunResult]:
    """One batched run per policy over the same instance batch."""
    return {p: run_batch(batch, p, pdeps, max_bins, max_bins_cap,
                         backend=backend, shard=shard,
                         block_events=block_events,
                         trace_level=trace_level)
            for p in policies}
