"""Chunked batched replay with interleaved consolidation planning.

The scan cannot decide migrations itself - the planner needs a global
look at the pool (which bins are nearly empty, where their items could
go) - so the driver alternates device and host:

    [K-event scan chunk] -> host planner on the carry -> [MIGRATE chunk]
        -> [next K-event chunk] -> ...

Each chunk threads the replay carry (``_replay_batch(...,
return_carry=True)``); MIGRATE chunks replay with ``migrate=True`` so
the MIGRATE branch is compiled only where migrations can occur, and the
base chunks keep the exact non-consolidating graph.  PAD no-ops make
ragged per-lane migration counts rectangular, exactly like the tail
padding of the base stream.

The planner input is the carry itself (loads / counts / alive /
open_seq / item placements), viewed in float64 - the same snapshot the
sequential oracle takes of its ``BinPool``, so with fp32-exact instances
both sides emit identical MIGRATE events and the replay stays
decision-for-decision equal (tests/test_consolidate.py).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import numpy as np

from .. import obs
from ..core.jaxsim import _replay_batch, replay_event_extras
from ..kernels import fitscore as _fk
from ..kernels.fitscore import ARRIVAL_KIND, DEPARTURE_KIND, MIGRATE_KIND, \
    PAD_KIND
from .planner import plan_migrations, should_plan
from .spec import ConsolidationSpec

# MIGRATE chunk widths round up to a multiple of this (PAD-filled) so the
# jitted segment retraces on a few width buckets, not every plan size.
_MIG_PAD = 8


@partial(jax.jit, static_argnames=("policy", "max_bins", "backend",
                                   "block_events", "migrate"))
def _segment(sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps,
             n_items, carry0, ev_extra, *, policy: str, max_bins: int,
             backend: str, block_events: int, migrate: bool):
    return _replay_batch(
        sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps, n_items,
        policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, carry0=carry0, return_carry=True,
        ev_extra=ev_extra if ev_extra else None, migrate=migrate)


def _pool_view(carry, d: int, backend: str) -> Dict[str, np.ndarray]:
    """Planner-facing float64 view of every replay carry layout: the
    packed kernel dict (event-blocked path) or the core tuple, whose loads
    are (L, slots, d) on the jnp backend and (L, dsub, Np) on the kernel
    ones."""
    if isinstance(carry, dict):
        sloti = np.asarray(carry["sloti"])
        return {"loads": np.asarray(carry["loads"])[..., :d]
                .astype(np.float64),
                "counts": sloti[..., _fk.SLOTI_COUNTS],
                "alive": sloti[..., _fk.SLOTI_ALIVE] > 0,
                "open_seq": sloti[..., _fk.SLOTI_OSEQ],
                "placements": np.asarray(carry["itemi"])
                [..., _fk.ITEMI_PLACE]}
    core, _cat = carry
    loads = np.asarray(core[0])
    if backend != "jnp":
        loads = np.swapaxes(loads, 1, 2)
    return {"loads": loads[..., :d].astype(np.float64),
            "counts": np.asarray(core[1]),
            "alive": np.asarray(core[2]),
            "open_seq": np.asarray(core[3]),
            "placements": np.asarray(core[7])}


def consolidated_replay(sizes, times, kinds, items, pdeps, dmask,
                        arrivals, rdeps, n_items, *, policy: str,
                        max_bins: int, backend: str = "jnp",
                        block_events: int = 0,
                        spec: ConsolidationSpec):
    """Batched replay of ``L`` lanes with consolidation interleaved.

    Same array contract as ``core.jaxsim._replay_batch``; returns
    ``(usage, opened, placements, overflow, stats)`` where ``stats``
    holds per-lane churn: ``migrations``, ``bins_closed``,
    ``budget_exhausted``, ``migration_cost`` and the emitted ``events``
    (per lane, ``(t, item)`` in emission order).
    """
    assert spec.enabled, "consolidated_replay needs an active spec; " \
        "disabled runs go straight through _replay_batch"
    sizes = np.asarray(sizes)
    L, n_max, d = sizes.shape
    E = int(times.shape[1])
    K = int(spec.every)
    times_np = np.asarray(times, np.float64)
    kinds_np = np.asarray(kinds)
    items_np = np.asarray(items)
    sizes64 = sizes.astype(np.float64)

    # full-event-axis per-event extras (RCP's distinct-category cumsum
    # must span chunks - same rule as checkpointed replay)
    extras = tuple(np.asarray(x) for x in replay_event_extras(
        policy, sizes, pdeps, dmask, arrivals, rdeps, n_items, times,
        kinds, items))

    seg = partial(_segment, policy=policy, max_bins=max_bins,
                  backend=backend, block_events=block_events)
    base = (pdeps, dmask, arrivals, rdeps, n_items)

    live: List[set] = [set() for _ in range(L)]
    last_t = np.zeros(L)
    budget_left = np.full(L, spec.budget, np.int64)
    t_next = np.zeros(L)
    migrations = np.zeros(L, np.int64)
    bins_closed = np.zeros(L, np.int64)
    budget_exh = np.zeros(L, np.int64)
    events: List[List] = [[] for _ in range(L)]

    carry = None
    out = None
    with obs.span("consolidate.replay", cat="consolidate", policy=policy,
                  spec=spec.canonical(), lanes=L):
        for s in range(0, E, K):
            e = min(s + K, E)
            ex = tuple(x[:, s:e] for x in extras)
            out = seg(sizes, times[:, s:e], kinds[:, s:e], items[:, s:e],
                      *base, carry, ex, migrate=False)
            carry = out[4]
            # host aliveness + lane clocks from the chunk's event prefix
            for lane in range(L):
                for i in range(s, e):
                    k = int(kinds_np[lane, i])
                    if k == ARRIVAL_KIND:
                        live[lane].add(int(items_np[lane, i]))
                    elif k == DEPARTURE_KIND:
                        live[lane].discard(int(items_np[lane, i]))
                    else:
                        continue
                    last_t[lane] = times_np[lane, i]
            if e >= E:
                break   # never plan after the final chunk
            view = _pool_view(carry, d, backend)
            plans: List[List[int]] = []
            for lane in range(L):
                run, t_next[lane] = should_plan(
                    spec, float(last_t[lane]), float(t_next[lane]))
                if not run or not live[lane]:
                    plans.append([])
                    continue
                bin_items: Dict[int, List[int]] = {}
                for item in sorted(live[lane]):
                    bin_items.setdefault(
                        int(view["placements"][lane, item]), []).append(item)
                plan = plan_migrations(
                    view["loads"][lane], view["counts"][lane],
                    view["alive"][lane], view["open_seq"][lane],
                    bin_items, sizes64[lane], threshold=spec.threshold,
                    budget=int(budget_left[lane]))
                bins_closed[lane] += plan.bins_closed
                budget_exh[lane] += plan.budget_exhausted
                migrations[lane] += len(plan.items)
                if budget_left[lane] >= 0:
                    budget_left[lane] -= len(plan.items)
                events[lane].extend(
                    (float(last_t[lane]), it) for it in plan.items)
                plans.append(plan.items)
            w = max(len(p) for p in plans)
            if not w:
                continue
            wp = -(-w // _MIG_PAD) * _MIG_PAD
            m_times = np.repeat(last_t[:, None], wp, axis=1)
            m_kinds = np.full((L, wp), PAD_KIND, kinds_np.dtype)
            m_items = np.zeros((L, wp), items_np.dtype)
            for lane, p in enumerate(plans):
                m_kinds[lane, :len(p)] = MIGRATE_KIND
                m_items[lane, :len(p)] = p
            # extras at a migrate boundary: the running value as of the
            # chunk's last event (MIGRATE events never advance them)
            m_ex = tuple(np.repeat(x[:, e - 1:e], wp, axis=1)
                         for x in extras)
            out = seg(sizes, m_times.astype(times_np.dtype), m_kinds,
                      m_items, *base, carry, m_ex, migrate=True)
            carry = out[4]
            obs.instant("consolidate.plan", chunk_end=int(e),
                        migrations=int(sum(len(p) for p in plans)),
                        bins_closed=int(bins_closed.sum()))
    obs.counter_add("consolidate.migrations", int(migrations.sum()))
    obs.counter_add("consolidate.bins_closed", int(bins_closed.sum()))
    obs.counter_add("consolidate.budget_exhausted", int(budget_exh.sum()))
    usage, opened, placements, overflow = out[:4]
    stats = {"migrations": migrations, "bins_closed": bins_closed,
             "budget_exhausted": budget_exh,
             "migration_cost": spec.cost * migrations.astype(np.float64),
             "events": events}
    return usage, opened, placements, overflow, stats
