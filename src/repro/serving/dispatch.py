"""Batched admission: double-buffered block placement for the serving
front end.

``DVBPScheduler.place`` routes ONE request per kernel dispatch (the
event-blocked megakernel at T=1).  This module is the throughput path:
the ``AdmissionQueue`` accumulates pending requests and ``BlockDispatcher``
drains them as ONE ``fitscore_replay_block`` call - a block of T pending
arrivals (plus any departures that fired since the last dispatch) placed
in a single on-chip pass, with the fleet carry VMEM-resident and aliased
in -> out exactly as in the sweep scan.

**Live carry.**  The dispatcher owns a persistent single-lane packed
replay carry (``core.jaxsim.make_live_carry``).  Unlike the scheduler's
T=1 snapshot select (which disables the kernel's free-slot stage and lets
the host ``BinPool`` open bins), the live carry keeps real slot counts:
the kernel opens and closes slots itself, and the host keeps only a tiny
mirror mapping kernel slots to absolute replica ids for the fleet
accounting (replica-seconds, opened, peak).  Item rows come from a host
free list and are recycled only when the departure's block *resolves*:
the host reads the arrival's placement out of ``itemi[row]`` after the
fact, so the row must stay untouched while any block that references it
is still in flight.

**Double buffering.**  ``flush()`` enqueues the jitted block dispatch and
returns immediately (jax async dispatch): placement of batch k runs on
device while the host assembles batch k+1.  Up to ``depth`` blocks stay
in flight; ``_resolve()`` fences (``np.asarray`` readback ==
``block_until_ready``) only when the pipeline is full or results are
demanded.

**Fixed T geometries.**  Batches pad with ``PAD_KIND`` no-op events to a
small fixed set of block sizes (default 1/8/32/256), so the jit trace
count stays bounded; ``serving.jit_trace`` / ``serving.jit_cache_hit``
counters (off ``kernels.ops.dispatch_trace_count``) are the monitored
invariant: a warm pass over known geometries traces nothing
(``tests/test_dispatch.py::test_retrace_bounded_by_geometries``).

**Degradation ladder.**  Every dispatch crosses the ``serving.select``
fault seam per rung: the configured block engine, then the same engine
in a per-event T=1 loop - the step ticking a
``resilience.degrade_dispatch_<from>_<to>`` counter.  Only device faults
(``guard.is_degradable``) step down; a kernel the compiler refuses is a
bug and propagates.  Overflow (the pool
ran out of slots) regrows the carry (``grow_live_carry``, doubling
``max_bins``) and replays the failed block plus everything newer from the
saved pre-block carries - the streams are kept host-side until their
block resolves.

**Equivalence.**  Batched decisions are provably equal to the sequential
oracle: events enter the stream in global time order (the front end
force-drains the admission queue before enqueuing a departure), the
blocked kernel replays them one at a time on-chip
(tests/test_replay_block.py: blocked == per-event), and the per-event
kernel decisions match the host algorithm zoo (tests/test_serving.py,
tests/test_dispatch.py) - so a T=256 batch lands every request exactly
where one-at-a-time placement would have.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..resilience import faults, guard
from .admission import AdmissionQueue
from .scheduler import ReplicaCapacity, Request

DEFAULT_GEOMETRIES = (1, 8, 32, 256)


def _constants():
    """Kernel-layout constants, imported lazily so ``repro.serving`` stays
    importable without jax initialized."""
    from ..kernels import fitscore as fk
    return fk


@dataclasses.dataclass
class _Event:
    kind: int            # ARRIVAL_KIND / DEPARTURE_KIND
    rid: int
    row: int             # item row in the carry
    t: float
    pdep: float          # absolute (predicted) departure time
    size: np.ndarray
    cat: int = 0         # family category (cbd/cbdt/rcp/la)
    large: int = 0       # rcp: size exceeds 1/2 in some dimension
    x: int = 0           # rcp: running distinct-category count


@dataclasses.dataclass
class _Inflight:
    """One dispatched, unresolved block: everything needed to read back
    placements - or to replay the block after overflow / a device fault."""
    carry_in: dict
    carry_out: dict
    events: List[_Event]
    streams: tuple       # (ev_i, ev_f, ev_size) numpy, padded to T
    T: int
    rung: int            # ladder rung that dispatched it
    migrate: bool = False  # consolidation drain block (MIGRATE events)


class BlockDispatcher:
    """Blocks of pending events -> one megakernel call on a live carry."""

    def __init__(self, policy: str, caps: ReplicaCapacity = ReplicaCapacity(),
                 tps: float = 50.0, d: int = 3, max_bins: int = 64,
                 max_items: int = 1024,
                 geometries: Sequence[int] = DEFAULT_GEOMETRIES,
                 impl: str = "auto", depth: int = 2):
        from ..core.jaxsim import (_KERNEL_FAMILY, make_live_carry,
                                   policy_spec)
        self.policy = policy
        self.caps = caps
        self.tps = tps
        self.d = d
        self.max_bins = max_bins
        self.impl = impl
        self.depth = depth
        self.geometries = tuple(sorted(set(int(g) for g in geometries)))
        assert self.geometries and self.geometries[0] >= 1
        spec = policy_spec(policy)
        self._spec = spec
        self.family = _KERNEL_FAMILY[spec.family]
        assert not spec.adaptive_alpha, \
            f"{policy!r} (PPE guess-and-double) scores real durations at " \
            "departure - not observable on a live stream; use rcp/" \
            "rcp_modified"
        self._needs_pred = self.family in ("cbd", "rcp", "la")
        self._carry = make_live_carry(policy, max_bins, d, max_items)
        self._n_items = max_items
        self._free = list(range(max_items - 1, -1, -1))   # pop() -> row 0..
        self._pending: List[_Event] = []
        self._inflight: List[_Inflight] = []
        self._rid_arrival: Dict[int, _Event] = {}
        self._rcp_seen: set = set()
        # host mirror: kernel slot -> fleet accounting
        self._slot_count = np.zeros(self._carry["loads"].shape[1], np.int64)
        self._slot_replica = np.full(self._slot_count.shape, -1, np.int64)
        self._slot_opened_at = np.zeros(self._slot_count.shape)
        self._next_replica = 0
        self._rid_slot: Dict[int, int] = {}
        self._rid_wall: Dict[int, float] = {}
        self.placements: Dict[int, int] = {}
        self.latencies: List[float] = []
        self.replica_seconds = 0.0
        self.replicas_opened = 0
        self.peak_replicas = 0
        self._open_now = 0

    # --------------------------------------------------------- event intake
    def _categorize(self, pdur: Optional[float], t: float,
                    size: np.ndarray) -> Tuple[int, int, int]:
        """Per-arrival family constants from the shared host categorization
        functions - the same ones ``DVBPScheduler`` and the batched scan's
        ``_category_setup`` use, so all paths agree on class boundaries."""
        from ..core.algorithms.departure import departure_window
        from ..core.algorithms.duration import duration_class
        from ..core.algorithms.learned import geo_class, la_class
        fk = _constants()
        if self._needs_pred:
            assert pdur is not None, \
                f"{self.policy!r} needs predicted decode lengths"
        if self._spec.family == "cbd":
            return int(duration_class(pdur, self._spec.beta)), 0, 0
        if self._spec.family == "cbdt":
            return int(departure_window(t + pdur, self._spec.rho)), 0, 0
        if self.family == "rcp":
            cat = int(np.clip(geo_class(max(pdur, 0.0)), 0, fk.KCAT - 1))
            if cat not in self._rcp_seen:
                self._rcp_seen.add(cat)
            return cat, int(float(size.max()) > 0.5), len(self._rcp_seen)
        if self.family == "la":
            return int(la_class(pdur, self._spec.la_mode)), 0, 0
        return 0, 0, 0

    def enqueue_arrival(self, req: Request, now: float,
                        wall_t: Optional[float] = None) -> None:
        fk = _constants()
        size = req.size(self.caps)
        pdur = None if req.predicted_decode_len is None else \
            req.predicted_decode_len / self.tps
        pdep = now if pdur is None else now + pdur
        cat, large, x = self._categorize(pdur, now, size)
        if not self._free:
            self._grow_items()
        row = self._free.pop()
        self._rid_wall[req.rid] = time.perf_counter() if wall_t is None \
            else wall_t
        ev = _Event(fk.ARRIVAL_KIND, req.rid, row, now, pdep, size, cat,
                    large, x)
        self._rid_arrival[req.rid] = ev
        self._pending.append(ev)
        if len(self._pending) >= self.geometries[-1]:
            self.flush()

    def enqueue_departure(self, rid: int, now: float) -> None:
        """The request finished: append its departure event.  The arrival
        must already be enqueued (the front end force-drains admission
        before finishing, keeping the event stream in global time order).
        """
        fk = _constants()
        arr = self._rid_arrival.pop(rid, None)
        if arr is None:
            raise KeyError(
                f"finish({rid}) before its arrival was dispatched; the "
                "front end must drain admission first")
        self._pending.append(dataclasses.replace(
            arr, kind=fk.DEPARTURE_KIND, t=now,
            x=len(self._rcp_seen) if self.family == "rcp" else 0))
        # the row is NOT freed here: ``_resolve`` reads the arrival's
        # placement out of ``itemi[row]`` after the block retires, so the
        # row must stay untouched until the departure's block resolves -
        # freeing it now would let a newer arrival overwrite the cell
        # while the older block is still in flight
        if len(self._pending) >= self.geometries[-1]:
            self.flush()

    def _grow_items(self) -> None:
        from ..core.jaxsim import grow_live_items
        self.sync()   # simplest safe point: no in-flight carries to patch
        new = 2 * self._n_items
        self._carry = grow_live_items(self._carry, new)
        self._free = list(range(new - 1, self._n_items - 1, -1)) + self._free
        self._n_items = new

    # ------------------------------------------------------------- dispatch
    def _geometry(self, m: int) -> int:
        for g in self.geometries:
            if m <= g:
                return g
        return self.geometries[-1]

    def _streams(self, events: List[_Event], T: int) -> tuple:
        """Pack a block of events into the kernel's padded numpy streams
        (``PAD_KIND`` filler to the fixed geometry)."""
        fk = _constants()
        Np_d = self._carry["loads"].shape[2]
        kind = np.full((1, T), fk.PAD_KIND, np.int32)
        item = np.zeros((1, T), np.int32)
        t = np.zeros((1, T), np.float32)
        pdep = np.zeros((1, T), np.float32)
        size = np.zeros((1, T, Np_d), np.float32)
        cat = np.zeros((1, T), np.int32)
        large = np.zeros((1, T), np.int32)
        x = np.zeros((1, T), np.int32)
        for j, ev in enumerate(events):
            kind[0, j] = ev.kind
            item[0, j] = ev.row
            t[0, j] = ev.t
            pdep[0, j] = ev.pdep
            size[0, j, :self.d] = ev.size
            cat[0, j] = ev.cat
            large[0, j] = ev.large
            x[0, j] = ev.x
        ev_i = {"kind": kind, "item": item}
        ev_f = {"t": t, "pdep": pdep}
        if self.family in ("cbd", "la"):
            ev_i["cat"] = cat
        elif self.family == "rcp":
            ev_i["cat"] = cat
            ev_i["large"] = large
            ev_i["x"] = x
            ev_f["p2err"] = np.ones((1, T), np.float32)
        elif self.family == "adaptive":
            # open-ended streams never observe real durations, so the
            # departure error stays at 1.0 - exactly the host
            # AdaptiveSwitch's behavior on serving request ids
            ev_f["errmax"] = np.ones((1, T), np.float32)
        return ev_i, ev_f, size

    def _rungs(self) -> List[Tuple[str, str]]:
        """The whole block, then the same engine one event per call.  Both
        rungs run the configured engine: a device fault never moves a
        native kernel into interpret mode."""
        return [("block", self.impl), ("events", self.impl)]

    def _dispatch(self, carry, streams, T: int, start_rung: int = 0,
                  migrate: bool = False) -> Tuple[dict, int]:
        """Run the degradation ladder from ``start_rung``: each rung
        crosses the ``serving.select`` fault seam once, degradable errors
        step down with a ``resilience.degrade_dispatch_*`` counter.
        ``migrate=True`` compiles the MIGRATE branch in (consolidation
        drain blocks only, so plain traffic keeps its exact graph)."""
        import jax.numpy as jnp

        from ..kernels import ops
        ev_i, ev_f, ev_size = streams
        dmask = np.zeros((1, self._carry["loads"].shape[2]), np.float32)
        dmask[0, :self.d] = 1.0
        rungs = self._rungs()
        for i in range(start_rung, len(rungs)):
            label, impl = rungs[i]
            try:
                faults.fire("serving.select")
                before = ops.dispatch_trace_count()
                if label == "events":
                    # per-event fallback: the same kernel, one event per
                    # call - slower, simpler, synchronous in spirit
                    out = carry
                    for j in range(T):
                        evi1 = {k: v[:, j:j + 1] for k, v in ev_i.items()}
                        evf1 = {k: v[:, j:j + 1] for k, v in ev_f.items()}
                        out = ops.fitscore_replay_dispatch(
                            out, evi1, evf1, ev_size[:, j:j + 1],
                            jnp.asarray(dmask), policy=self.policy,
                            n=self.max_bins, d=self.d, impl=impl,
                            migrate=migrate)
                else:
                    out = ops.fitscore_replay_dispatch(
                        carry, ev_i, ev_f, ev_size, jnp.asarray(dmask),
                        policy=self.policy, n=self.max_bins, d=self.d,
                        impl=impl, migrate=migrate)
                retraced = ops.dispatch_trace_count() - before
                if retraced:
                    obs.counter_add("serving.jit_trace", retraced)
                else:
                    obs.counter_add("serving.jit_cache_hit")
                return out, i
            except Exception as e:
                if not guard.is_degradable(e) or i + 1 >= len(rungs):
                    raise
                nxt = rungs[i + 1][0]
                obs.counter_add(
                    f"resilience.degrade_dispatch_{label}_{nxt}")
                obs.instant("resilience.degrade_dispatch", frm=label,
                            to=nxt, error=str(e)[:200])
        raise AssertionError("unreachable: last rung re-raises")

    def flush(self) -> None:
        """Dispatch the pending events as one (or, past the largest
        geometry, several) padded block(s); returns without fencing -
        the block executes while the host assembles the next batch."""
        while self._pending:
            chunk = self._pending[:self.geometries[-1]]
            del self._pending[:len(chunk)]
            T = self._geometry(len(chunk))
            obs.counter_hist("serving.dispatch_batch_size", len(chunk))
            streams = self._streams(chunk, T)
            with obs.span("serving.dispatch", T=T, events=len(chunk),
                          policy=self.policy):
                out, rung = self._dispatch(self._carry, streams, T)
            self._inflight.append(_Inflight(self._carry, out, chunk,
                                            streams, T, rung))
            self._carry = out
            while len(self._inflight) > self.depth:
                self._resolve()

    # -------------------------------------------------------------- resolve
    def _readback(self, rec: _Inflight) -> np.ndarray:
        """Fence on the block's carry; returns per-item placements.
        Raises on device failure (caught by ``_resolve`` for replay)."""
        fk = _constants()
        itemi = np.asarray(rec.carry_out["itemi"][0, :, fk.ITEMI_PLACE])
        si = np.asarray(rec.carry_out["si"][0])
        if si[fk.SI_OVERFLOW] > 0:
            from ..core.jaxsim import CapacityError
            raise CapacityError(
                f"live carry overflowed {self.max_bins} slots",
                policy=self.policy, max_bins=self.max_bins)
        return itemi

    def _resolve(self) -> None:
        """Retire the oldest in-flight block: read back its placements and
        update the host replica mirror.  Overflow and degradable device
        errors replay the block (grown carry for overflow) plus every
        newer in-flight block from the saved pre-block carries."""
        from ..core.jaxsim import (CapacityError, MAX_BINS_CAP,
                                   grow_live_carry, grow_max_bins)
        fk = _constants()
        rec = self._inflight[0]
        while True:
            try:
                with obs.span("serving.resolve", T=rec.T,
                              events=len(rec.events)):
                    itemi = self._readback(rec)
                break
            except CapacityError:
                if self.max_bins >= MAX_BINS_CAP:
                    raise
                self.max_bins = grow_max_bins(self.max_bins)
                obs.counter_add("serving.carry_regrow")
                self._replay_from(0, grow=True)
                rec = self._inflight[0]
            except Exception as e:
                if not guard.is_degradable(e) or \
                        rec.rung + 1 >= len(self._rungs()):
                    raise
                obs.counter_add("resilience.degrade_dispatch_resolve")
                self._replay_from(0, grow=False,
                                  start_rung=rec.rung + 1)
                rec = self._inflight[0]
        self._inflight.pop(0)
        now_wall = time.perf_counter()
        for ev in rec.events:
            if ev.kind == fk.ARRIVAL_KIND:
                slot = int(itemi[ev.row])
                assert slot >= 0, "arrival unplaced without overflow"
                self._mirror_place(slot, ev)
                t0 = self._rid_wall.pop(ev.rid, None)
                if t0 is not None:
                    self.latencies.append(now_wall - t0)
            elif ev.kind == fk.MIGRATE_KIND:
                # departure half: leave the source slot (closing it if
                # the migrant was the last occupant) ...
                self._mirror_depart(self._rid_slot.pop(ev.rid), ev.t)
                # ... arrival half: land on the kernel's re-place (the
                # source slot was excluded from its select)
                slot = int(itemi[ev.row])
                assert slot >= 0, "migrant unplaced without overflow"
                self._mirror_place(slot, ev)
            else:
                self._mirror_depart(self._rid_slot.pop(ev.rid), ev.t)
                self._free.append(ev.row)

    def _mirror_place(self, slot: int, ev: _Event) -> None:
        if self._slot_count[slot] == 0:
            self._slot_replica[slot] = self._next_replica
            self._next_replica += 1
            self._slot_opened_at[slot] = ev.t
            self.replicas_opened += 1
            self._open_now += 1
            self.peak_replicas = max(self.peak_replicas, self._open_now)
        self._slot_count[slot] += 1
        self._rid_slot[ev.rid] = slot
        self.placements[ev.rid] = int(self._slot_replica[slot])

    def _mirror_depart(self, slot: int, t: float) -> None:
        self._slot_count[slot] -= 1
        if self._slot_count[slot] == 0:
            self.replica_seconds += t - self._slot_opened_at[slot]
            self._open_now -= 1

    def _replay_from(self, i: int, grow: bool, start_rung: int = 0) -> None:
        """Re-dispatch in-flight blocks ``i..`` from block ``i``'s saved
        pre-block carry - after growing the pool (overflow) or stepping
        down the ladder (device fault)."""
        from ..core.jaxsim import grow_live_carry
        carry = self._inflight[i].carry_in
        if grow:
            carry = grow_live_carry(carry, self.max_bins, self.d)
            # the mirror arrays track slots; grow them alongside
            Np = carry["loads"].shape[1]
            if Np > self._slot_count.shape[0]:
                pad = Np - self._slot_count.shape[0]
                self._slot_count = np.concatenate(
                    [self._slot_count, np.zeros(pad, np.int64)])
                self._slot_replica = np.concatenate(
                    [self._slot_replica, np.full(pad, -1, np.int64)])
                self._slot_opened_at = np.concatenate(
                    [self._slot_opened_at, np.zeros(pad)])
        # the event streams are geometry-stable under slot growth (dpad
        # depends only on d), so saved streams re-dispatch as-is
        for k in range(i, len(self._inflight)):
            rec = self._inflight[k]
            out, rung = self._dispatch(carry, rec.streams, rec.T,
                                       start_rung if k == i else 0,
                                       migrate=rec.migrate)
            rec.carry_in, rec.carry_out, rec.rung = carry, out, rung
            carry = out
        self._carry = carry

    def sync(self) -> None:
        """Flush pending events and fence every in-flight block."""
        self.flush()
        while self._inflight:
            self._resolve()

    # -------------------------------------------------------- consolidation
    def consolidate(self, now: float, spec) -> Dict[str, int]:
        """Opt-in consolidation drain pass over the live fleet.

        Quiesces the pipeline (``sync``), runs the SAME planner as the
        batched driver and the host oracle (``consolidate.plan_migrations``)
        on the live carry's pool snapshot, and dispatches the plan as
        MIGRATE blocks (``migrate=True`` compiles the branch in only
        here - plain traffic keeps its exact graph).  Each migrant leaves
        its source replica (closing it when it was the last occupant) and
        is re-placed by the policy's own select with the source slot
        excluded.  Resolves before returning, so ``placements`` /
        ``replica_seconds`` reflect the drain; returns the churn stats
        (``migrations`` / ``bins_closed`` / ``budget_exhausted``)."""
        from ..consolidate import ConsolidationSpec, plan_migrations
        fk = _constants()
        if isinstance(spec, str):
            spec = ConsolidationSpec.parse(spec)
        assert spec.enabled, "consolidate() needs an active spec"
        self.sync()   # plan on a quiesced carry: nothing in flight
        sloti = np.asarray(self._carry["sloti"][0])
        loads = np.asarray(
            self._carry["loads"][0, :, :self.d]).astype(np.float64)
        bin_items: Dict[int, List[int]] = {}
        row_ev: Dict[int, _Event] = {}
        sizes = np.zeros((self._n_items, self.d))
        for rid in sorted(self._rid_slot):
            arr = self._rid_arrival.get(rid)
            assert arr is not None, \
                "live rid without a stored arrival after sync()"
            bin_items.setdefault(
                int(self._rid_slot[rid]), []).append(arr.row)
            row_ev[arr.row] = arr
            sizes[arr.row] = arr.size
        plan = plan_migrations(
            loads, sloti[:, fk.SLOTI_COUNTS],
            sloti[:, fk.SLOTI_ALIVE] > 0, sloti[:, fk.SLOTI_OSEQ],
            bin_items, sizes, threshold=spec.threshold,
            budget=spec.budget)
        stats = {"migrations": len(plan.items),
                 "bins_closed": plan.bins_closed,
                 "budget_exhausted": plan.budget_exhausted}
        obs.counter_add("consolidate.migrations", len(plan.items))
        obs.counter_add("consolidate.bins_closed", plan.bins_closed)
        obs.counter_add("consolidate.budget_exhausted",
                        plan.budget_exhausted)
        if not plan.items:
            return stats
        events = [dataclasses.replace(
            row_ev[row], kind=fk.MIGRATE_KIND, t=now,
            x=len(self._rcp_seen) if self.family == "rcp" else 0)
            for row in plan.items]
        with obs.span("serving.consolidate", policy=self.policy,
                      migrations=len(events),
                      bins_closed=plan.bins_closed):
            while events:
                chunk = events[:self.geometries[-1]]
                del events[:len(chunk)]
                T = self._geometry(len(chunk))
                streams = self._streams(chunk, T)
                out, rung = self._dispatch(self._carry, streams, T,
                                           migrate=True)
                self._inflight.append(_Inflight(
                    self._carry, out, chunk, streams, T, rung,
                    migrate=True))
                self._carry = out
            while self._inflight:
                self._resolve()
        return stats


class BatchedFrontEnd:
    """Admission -> batched dispatch: the online serving pipeline.

    ``submit`` feeds the bounded ``AdmissionQueue``; ``tick`` drains up to
    ``batch_max`` survivors into the dispatcher as one block; ``finish``
    force-drains admission first (keeping the event stream in global time
    order - the equivalence precondition) and then enqueues the departure.
    ``sync`` fences the pipeline; decisions land in ``placements`` (rid ->
    replica id in opening order, directly comparable to
    ``DVBPScheduler.place``'s absolute bin indices)."""

    def __init__(self, policy: str,
                 caps: ReplicaCapacity = ReplicaCapacity(),
                 tps: float = 50.0, max_pending: int = 4096,
                 deadline: float = 1e9, batch_max: int = 256,
                 geometries: Sequence[int] = DEFAULT_GEOMETRIES,
                 impl: str = "auto", max_bins: int = 64,
                 max_items: int = 1024, depth: int = 2):
        self.dispatcher = BlockDispatcher(
            policy, caps, tps, max_bins=max_bins, max_items=max_items,
            geometries=geometries, impl=impl, depth=depth)
        self.queue = AdmissionQueue(None, max_pending=max_pending,
                                    deadline=deadline, batch_max=batch_max)
        self.batch_max = batch_max
        # admission wall clock per rid: the p50/p99 admission-to-placement
        # latency starts here, not at dispatcher enqueue
        self._wall: Dict[int, float] = {}
        # arrivals handed to the dispatcher since its last flush: the
        # ``finish`` path drains admission continuously (keeping the queue
        # short), so the batch trigger counts hand-overs, not queue depth
        self._since_flush = 0

    def submit(self, req: Request, now: float) -> bool:
        wall = time.perf_counter()
        ok = self.queue.submit(req, now)
        if ok:
            self._wall[req.rid] = wall
            if len(self.queue) >= self.batch_max:
                self.tick(now)
        return ok

    def _hand_over(self, req: Request, t_in: float) -> None:
        # the arrival event carries the request's own (submit) time, not
        # the drain time - exactly what the sequential oracle sees when it
        # places each request at its arrival, so batched decisions stay
        # comparable decision-for-decision
        self.dispatcher.enqueue_arrival(req, t_in,
                                        wall_t=self._wall.pop(req.rid, None))
        self.queue.stats.placed += 1
        self._since_flush += 1

    def tick(self, now: float) -> int:
        """Drain one admission batch into the dispatcher; returns how many
        requests were dispatched."""
        obs.counter_hist("serving.queue_depth", len(self.queue))
        batch = self.queue.take(now)
        for req, t_in in batch:
            self._hand_over(req, t_in)
        if batch:
            self.dispatcher.flush()
            self._since_flush = 0
        return len(batch)

    def finish(self, rid: int, now: float) -> None:
        """The request's decode completed.  Every queued arrival precedes
        this departure in sim time, so drain them all first."""
        while len(self.queue):
            for req, t_in in self.queue.take(now, limit=len(self.queue)):
                self._hand_over(req, t_in)
        self.dispatcher.enqueue_departure(rid, now)
        if self._since_flush >= self.batch_max:
            self.dispatcher.flush()
            self._since_flush = 0

    def sync(self) -> None:
        self.dispatcher.sync()

    def consolidate(self, now: float, spec) -> Dict[str, int]:
        """Run one consolidation drain pass on the dispatcher (see
        ``BlockDispatcher.consolidate``)."""
        return self.dispatcher.consolidate(now, spec)

    @property
    def placements(self) -> Dict[int, int]:
        return self.dispatcher.placements

    @property
    def latencies(self) -> List[float]:
        return self.dispatcher.latencies


@dataclasses.dataclass
class ServeReport:
    policy: str
    n_requests: int
    placed: int
    shed: int
    replica_seconds: float
    replicas_opened: int
    peak_replicas: int
    wall_seconds: float
    latencies: List[float]
    placements: Dict[int, int]
    metrics: Dict[str, float]

    @property
    def throughput(self) -> float:
        return self.placed / self.wall_seconds if self.wall_seconds else 0.0

    def latency_quantiles(self, qs=(0.5, 0.99)) -> List[float]:
        lat = np.sort(np.asarray(self.latencies))
        if lat.size == 0:
            return [0.0 for _ in qs]
        return [float(np.quantile(lat, q)) for q in qs]


def serve_traffic(reqs: List[Request], policy: str,
                  caps: ReplicaCapacity = ReplicaCapacity(),
                  tps: float = 50.0, batch_max: int = 256,
                  geometries: Sequence[int] = DEFAULT_GEOMETRIES,
                  impl: str = "auto", max_bins: int = 64,
                  max_items: int = 1024, deadline: float = 1e9,
                  depth: int = 2) -> ServeReport:
    """Drive the batched front end through a request trace, event-driven
    exactly like the sequential oracle (``fleet.simulate_fleet``):
    departures with earlier sim time fire before the next arrival, so the
    dispatcher's event stream - and therefore every placement - matches
    one-at-a-time replay decision-for-decision."""
    counters0 = obs.counters()
    fe = BatchedFrontEnd(policy, caps, tps, batch_max=batch_max,
                         geometries=geometries, impl=impl,
                         max_bins=max_bins, max_items=max_items,
                         deadline=deadline, depth=depth)
    t0 = time.perf_counter()
    heap: List[Tuple[float, int]] = []
    for r in sorted(reqs, key=lambda x: x.arrival):
        while heap and heap[0][0] <= r.arrival:
            ft, rid = heapq.heappop(heap)
            fe.finish(rid, ft)
        if fe.submit(r, r.arrival):
            heapq.heappush(heap, (r.arrival + r.decode_len / tps, r.rid))
    while heap:
        ft, rid = heapq.heappop(heap)
        fe.finish(rid, ft)
    fe.sync()
    wall = time.perf_counter() - t0
    dp = fe.dispatcher
    return ServeReport(
        policy=policy, n_requests=len(reqs),
        placed=len(dp.placements), shed=fe.queue.stats.shed,
        replica_seconds=dp.replica_seconds,
        replicas_opened=dp.replicas_opened,
        peak_replicas=dp.peak_replicas, wall_seconds=wall,
        latencies=list(dp.latencies), placements=dict(dp.placements),
        metrics=obs.counter_deltas(counters0))
