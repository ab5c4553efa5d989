"""Bounded-memory event sources and the fixed-geometry chunk builder.

The in-memory replay materializes one padded ``(L, 2 n_max)`` event tensor
per lane, so memory grows with trace *length*.  This module turns a
request stream (arrival-sorted ``(size, arrival, departure[, predicted])``
records) into a sequence of fixed-geometry :class:`EventChunk` s of ``C``
events each, with item metadata held in a recycled *row pool*: an arriving
VM is assigned a pool row, a departing VM frees it, and a freed row becomes
allocatable again from the *next* chunk on (never inside the chunk that
freed it, so the chunk's pool scatter happens once, up front).  Peak pool
size is therefore O(max concurrently alive VMs), not O(trace length).

The builder works a block of records at a time, in NumPy: one conversion
per column, one ``np.lexsort`` merging the block's arrivals with the due
departures (pending departures are sorted parallel arrays, not a heap),
and whole-slice writes into each chunk.  Its memory is O(alive VMs + one
block).

Event ordering is bit-compatible with ``core.jaxsim.event_sequence``:
events sort by time (compared in float64, exactly as the in-memory
``np.lexsort`` does before the device cast to float32), departures before
arrivals at equal times, equal-time departures by item sequence number and
equal-time arrivals in source order.  Chunks are padded to ``C`` with
``PAD_KIND`` no-op events - the replay carry passes through them unchanged,
so padding never affects decisions and every chunk shares one jit trace.

Two policy families need care beyond the elementwise per-item constants
(``jaxsim._category_setup`` derives those from the pool's size / arrival /
departure rows, so a correctly scattered pool reproduces them exactly):

  * RCP's running distinct-category count is a cumsum over the whole event
    axis; the builder maintains it on the host (``geo_class`` twin on
    float32 durations, the exact dtype path of the device computation) and
    ships it per chunk as the ``ev_extra`` stream.
  * Hybrid builds its key table from the *whole* instance up front
    (clairvoyant, like ``make_live_carry``'s serving prohibition), so it
    streams in *identity* mode: events are chunked but the item table is
    the full instance - memory O(n_items), still free of the O(2 n_max)
    event tensor.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
from typing import Iterator, Optional, Tuple

import numpy as np

from ..core.algorithms.learned import geo_class
from ..core.jaxsim import policy_spec
from ..core.types import Instance
from ..kernels.fitscore import ARRIVAL_KIND, DEPARTURE_KIND, KCAT, PAD_KIND

# Scatter index for padding rows of the per-chunk pool update: far out of
# range, dropped by the device scatter's mode="drop".
POOL_SENTINEL = np.int32(2 ** 30)

# Records the chunk builder takes from its source at a time: each block is
# converted once per column and merged by one sort, so the builder's memory
# is O(alive VMs + one block).
RECORD_BLOCK = 4096


@dataclasses.dataclass(frozen=True)
class StreamMeta:
    """Static facts about a request stream.

    ``fingerprint`` identifies the stream content + order (checkpoint
    digests); ``n_items`` is the total request count when the source knows
    it up front, else -1 (a CSV stream discovers it only by draining)."""
    d: int
    fingerprint: str
    n_items: int = -1


class InstanceSource:
    """Stream one in-memory :class:`Instance` (arrival-sorted), optionally
    with predicted durations - the bit-equality reference source and the
    bridge from every existing suite generator."""

    def __init__(self, inst: Instance,
                 predicted_durations: Optional[np.ndarray] = None):
        assert np.all(np.diff(inst.arrivals) >= 0), \
            f"{inst.name!r} is not arrival-sorted; use .sorted_by_arrival()"
        self.inst = inst
        self.pdeps = inst.departures if predicted_durations is None \
            else inst.arrivals + np.asarray(predicted_durations, np.float64)

    def meta(self) -> StreamMeta:
        from ..sweep.batching import instance_digest
        h = hashlib.blake2b(digest_size=8)
        h.update(instance_digest(self.inst).encode())
        h.update(np.ascontiguousarray(self.pdeps).tobytes())
        return StreamMeta(self.inst.d, h.hexdigest(), self.inst.n_items)

    def records(self) -> Iterator[Tuple[np.ndarray, float, float, float]]:
        inst = self.inst
        for s in range(0, inst.n_items, RECORD_BLOCK):
            e = s + RECORD_BLOCK
            yield from zip(inst.sizes[s:e], inst.arrivals[s:e].tolist(),
                           inst.departures[s:e].tolist(),
                           self.pdeps[s:e].tolist())

    def full_arrays(self):
        """(sizes, arrivals, rdeps, pdeps) float64 - identity (hybrid)
        mode's whole-instance item table."""
        return (self.inst.sizes, self.inst.arrivals, self.inst.departures,
                np.asarray(self.pdeps, np.float64))


class CsvSource:
    """Stream Azure-format requests (``data.traces.iter_azure_requests``)
    for one machineId without ever materializing the trace."""

    def __init__(self, root: str, machine_id: int = 0):
        self.root, self.machine_id = root, int(machine_id)

    def meta(self) -> StreamMeta:
        from ..data.traces import azure_stream_meta
        d = azure_stream_meta(self.root, self.machine_id)
        return StreamMeta(
            d, f"azure:{self.root}:pm{self.machine_id}", -1)

    def records(self):
        from ..data.traces import iter_azure_requests
        for size, arr, dep in iter_azure_requests(self.root,
                                                  self.machine_id):
            yield size, arr, dep, dep   # clairvoyant predictions


@dataclasses.dataclass
class EventChunk:
    """One fixed-geometry unit of device work: ``C`` merged events plus the
    pool-row scatter that makes their item metadata resolvable.

    ``times``/``kinds``/``items`` are the (C,) event streams (float32 /
    int32, PAD-padded); ``upd_*`` the (C,)-shaped pool update for rows
    first written in this chunk (``POOL_SENTINEL`` index padding - a row's
    constants are scattered exactly once, in the chunk its VM arrives);
    ``extras`` the per-event ``ev_extra`` streams (RCP's running count);
    ``freed``/``freed_seqs`` the rows released by this chunk's departures
    and the global item sequence numbers that owned them (placement
    harvest - those rows may be recycled from the next chunk on).
    ``item_rows`` is the pool size this chunk's rows require (mid-chunk
    growth included - the driver grows pool + carry *before* replaying
    the chunk whenever it increases)."""
    times: np.ndarray
    kinds: np.ndarray
    items: np.ndarray
    n_events: int
    upd_idx: np.ndarray
    upd_size: np.ndarray
    upd_arrival: np.ndarray
    upd_rdep: np.ndarray
    upd_pdep: np.ndarray
    extras: Tuple[np.ndarray, ...]
    freed: np.ndarray
    freed_seqs: np.ndarray
    item_rows: int
    final: bool


class _OpenChunk:
    """The chunk being filled: its (C,) event and pool-update columns, PAD
    and ``POOL_SENTINEL`` padded, and the rows its departures freed."""

    def __init__(self, C: int, d: int, x0: int):
        self.times = np.zeros(C, np.float32)
        self.kinds = np.full(C, PAD_KIND, np.int32)
        self.items = np.zeros(C, np.int32)
        self.extra = np.full(C, x0, np.int32)   # PADs carry the start value
        self.upd_idx = np.full(C, POOL_SENTINEL, np.int32)
        self.upd_size = np.zeros((C, d), np.float32)
        self.upd_arrival = np.zeros(C, np.float32)
        self.upd_rdep = np.zeros(C, np.float32)
        self.upd_pdep = np.zeros(C, np.float32)
        self.freed: list = []
        self.freed_seqs: list = []
        self.fill = 0               # events written
        self.nupd = 0               # pool updates written


def _columns(recs, d: int):
    """(sizes f32 (n, d), arrivals, rdeps, pdeps f64) of a block of
    records: one conversion per column."""
    size, arr, rdep, pdep = zip(*recs)
    return (np.array(size, np.float32)[:, :d], np.array(arr, np.float64),
            np.array(rdep, np.float64), np.array(pdep, np.float64))


def _check_block(arr, rdep, last_arr: float) -> None:
    """The per-record checks, in record order: arrival-sorted (across
    blocks too), then departure after arrival."""
    prev = np.concatenate(([last_arr], arr[:-1]))
    unsorted = arr < prev
    bad = unsorted | ~(rdep > arr)
    if bad.any():
        i = int(np.argmax(bad))
        if unsorted[i]:
            raise ValueError(
                f"stream not arrival-sorted: {arr[i]} after {prev[i]}")
        raise ValueError(f"departure {rdep[i]} <= arrival {arr[i]}")


class ChunkedWorkload:
    """Merge a request stream into arrival/departure events and cut them
    into :class:`EventChunk` s over a recycled row pool.

    Records are pulled ``record_block`` at a time.  Pending departures are
    parallel arrays sorted by ``(departure_time, item_seq)``.  A block's
    arrivals are merged with every pending or in-block departure whose
    time is <= the block's last arrival by one ``np.lexsort`` on (time,
    departures before arrivals, item seq); later departures stay pending.
    This reproduces the in-memory event order exactly (time, then
    departures-before-arrivals, then source position).  The merged events
    fill chunks in slices: a chunk's arrivals take, in arrival order, the
    smallest rows freed by earlier chunks, then fresh rows.  Memory is
    O(alive VMs + one block), never O(trace length).  ``grow`` doubles the
    pool when the alive population outruns it (the driver re-traces once
    per growth); identity mode disables recycling and pins ``item_rows``
    to the full item count."""

    record_block = RECORD_BLOCK

    def __init__(self, source, policy: str, *, chunk_events: int = 2048,
                 item_rows: int = 256, grow: bool = True,
                 identity: bool = False):
        spec = policy_spec(policy)
        self.source = source
        self.spec = spec
        self.chunk_events = int(chunk_events)
        self.identity = bool(identity or spec.family == "hybrid")
        if self.identity:
            n = source.meta().n_items
            assert n >= 0, \
                f"{policy!r} streams in identity (whole-table) mode, " \
                "which needs a source with a known item count"
            item_rows = max(int(n), 1)
            grow = False
        self.item_rows = max(int(item_rows), 1)
        self.grow = bool(grow)
        self.d = source.meta().d
        # live pool state (populated while chunks() runs)
        self._row_seq = np.full(self.item_rows, -1, np.int64)  # alive seq
        self._seq_count = 0         # items placed into chunks
        self.records_pulled = 0     # records taken from the source

    # ------------------------------------------------------------ builder
    def chunks(self) -> Iterator[EventChunk]:
        C, d = self.chunk_events, self.d
        B = max(int(self.record_block), 1)
        rcp = self.spec.family == "rcp"
        records = iter(self.source.records())
        # pending departures, sorted by (time, seq), rows assigned
        pend_t = np.empty(0, np.float64)
        pend_seq = np.empty(0, np.int64)
        pend_row = np.empty(0, np.int64)
        free = np.empty(0, np.int64)    # allocatable at the chunk's start
        used = 0                        # ... of which its arrivals took
        next_fresh = 0
        seen_cats = np.zeros(KCAT, bool)
        xcount = 0                      # RCP count after the merged events
        last_arr = -np.inf
        seq0 = 0                        # seq of the block's first record
        op = _OpenChunk(C, d, 0)

        def cut(final: bool) -> EventChunk:
            nonlocal op, free, used
            none = np.empty(0, np.int64)
            rows = np.concatenate([none, *op.freed])
            fr = np.full(C, POOL_SENTINEL, np.int32)
            fr[:len(rows)] = rows
            fseq = np.full(C, -1, np.int64)
            fseq[:len(rows)] = np.concatenate([none, *op.freed_seqs])
            chunk = EventChunk(
                op.times, op.kinds, op.items, op.fill, op.upd_idx,
                op.upd_size, op.upd_arrival, op.upd_rdep, op.upd_pdep,
                (op.extra,) if rcp else (), fr, fseq, self.item_rows, final)
            # rows freed by this chunk become allocatable from the next
            # chunk on - never inside it (the pool scatter is chunk-start)
            if not self.identity:
                free = np.sort(np.concatenate((free[used:], rows)))
                used = 0
            op = _OpenChunk(C, d, op.extra[-1])
            return chunk

        def alloc(seqs: np.ndarray) -> np.ndarray:
            """Rows of a slice's arrivals, in arrival order."""
            nonlocal used, next_fresh
            if self.identity:
                return seqs
            take = min(len(seqs), len(free) - used)
            fresh = len(seqs) - take
            if next_fresh + fresh > self.item_rows:
                if not self.grow:
                    seq = seqs[take + self.item_rows - next_fresh]
                    raise RuntimeError(
                        f"item-row pool exhausted ({self.item_rows} rows) "
                        f"at request #{seq} with grow=False; pass a larger "
                        "item_rows or grow=True")
                while next_fresh + fresh > self.item_rows:
                    self.item_rows *= 2
                self._row_seq = np.concatenate((self._row_seq, np.full(
                    self.item_rows - len(self._row_seq), -1, np.int64)))
            rows = np.concatenate((free[used:used + take], np.arange(
                next_fresh, next_fresh + fresh, dtype=np.int64)))
            used += take
            next_fresh += fresh
            return rows

        def fill(times, kinds, seqs, rows, extra, block):
            """Write merged events into chunks, cutting each one full.
            ``block`` (first seq, its rows, sizes, arrivals, rdeps, pdeps
            as float32) resolves arrivals and in-block departures; None
            for a stretch of pending departures."""
            pos, E = 0, len(times)
            while pos < E:
                end = min(pos + C - op.fill, E)
                kd, sq, rw = kinds[pos:end], seqs[pos:end], rows[pos:end]
                dep = kd == DEPARTURE_KIND
                if block is not None:
                    s0, brows, sizes, arr, rdep, pdep = block
                    arrived = ~dep
                    aseq = sq[arrived]
                    if len(aseq):
                        ai = aseq - s0
                        ar = alloc(aseq)
                        brows[ai] = ar
                        rw[arrived] = ar
                        self._row_seq[ar] = aseq
                        self._seq_count += len(ai)
                        u0 = op.nupd
                        u1 = op.nupd = u0 + len(ai)
                        op.upd_idx[u0:u1] = ar
                        op.upd_size[u0:u1] = sizes[ai]
                        op.upd_arrival[u0:u1] = arr[ai]
                        op.upd_rdep[u0:u1] = rdep[ai]
                        op.upd_pdep[u0:u1] = pdep[ai]
                    late = dep & (sq >= s0)     # arrived in this block
                    rw[late] = brows[sq[late] - s0]
                gone = rw[dep]
                op.freed.append(gone)
                op.freed_seqs.append(sq[dep])
                self._row_seq[gone] = -1
                f0 = op.fill
                f1 = op.fill = f0 + end - pos
                op.times[f0:f1] = times[pos:end]
                op.kinds[f0:f1] = kd
                op.items[f0:f1] = rw
                if rcp:
                    op.extra[f0:f1] = extra[pos:end]
                pos = end
                if f1 == C:
                    yield cut(False)

        while True:
            recs = list(itertools.islice(records, B))
            self.records_pulled += len(recs)
            if not recs:
                break
            n = len(recs)
            sizes, arr, rdep, pdep = _columns(recs, d)
            _check_block(arr, rdep, last_arr)
            last_arr = arr[-1]
            seqs = np.arange(seq0, seq0 + n, dtype=np.int64)
            # every departure at or before the block's last arrival is
            # merged now (equal times: departures first, by item seq)
            k = int(np.searchsorted(pend_t, last_arr, side="right"))
            now = rdep <= last_arr
            ndep = k + int(np.count_nonzero(now))
            t = np.concatenate((pend_t[:k], rdep[now], arr))
            sq = np.concatenate((pend_seq[:k], seqs[now], seqs))
            arrival = np.zeros(ndep + n, bool)
            arrival[ndep:] = True
            order = np.lexsort((sq, arrival, t))
            t, sq, arrival = t[order], sq[order], arrival[order]
            rows = np.concatenate((pend_row[:k],
                                   np.zeros(ndep - k + n, np.int64)))[order]
            kinds = np.where(arrival, ARRIVAL_KIND,
                             DEPARTURE_KIND).astype(np.int32)
            extra = None
            if rcp:
                # host twin of the device category: float32 duration
                # arithmetic end to end, frexp-exact class boundaries
                pdur = pdep.astype(np.float32) - arr.astype(np.float32)
                cat = np.clip(geo_class(np.maximum(pdur, np.float32(0.0))),
                              0, KCAT - 1)
                cats, first = np.unique(cat, return_index=True)
                new = ~seen_cats[cats]
                seen_cats[cats[new]] = True
                step = np.zeros(n, np.int32)
                step[first[new]] = 1
                inc = np.zeros(len(t), np.int32)
                inc[arrival] = step[sq[arrival] - seq0]
                extra = xcount + np.cumsum(inc, dtype=np.int32)
                xcount = int(extra[-1])
            brows = np.zeros(n, np.int64)
            yield from fill(t.astype(np.float32), kinds, sq, rows, extra,
                            (seq0, brows, sizes, arr.astype(np.float32),
                             rdep.astype(np.float32),
                             pdep.astype(np.float32)))
            later = ~now
            pt = np.concatenate((pend_t[k:], rdep[later]))
            ps = np.concatenate((pend_seq[k:], seqs[later]))
            pr = np.concatenate((pend_row[k:], brows[later]))
            order = np.lexsort((ps, pt))
            pend_t, pend_seq, pend_row = pt[order], ps[order], pr[order]
            seq0 += n
        # drain the tail departures
        yield from fill(pend_t.astype(np.float32),
                        np.full(len(pend_t), DEPARTURE_KIND, np.int32),
                        pend_seq, pend_row,
                        np.full(len(pend_t), xcount, np.int32), None)
        yield cut(True)

    # ----------------------------------------------------------- queries
    @property
    def n_items(self) -> int:
        """Items streamed so far (total once the stream is drained)."""
        return self._seq_count

    def live_rows(self):
        """{pool row: global item seq} still alive (empty after a full
        drain; non-empty only if iteration stopped early)."""
        rows = np.flatnonzero(self._row_seq >= 0)
        return dict(zip(rows.tolist(), self._row_seq[rows].tolist()))


def synthetic_source(n_items: int, d: int = 4, seed: int = 0,
                     pm_cores: int = 64, med_lifetime: float = 1800.0,
                     sigma_lifetime: float = 1.6,
                     name: str = "stream_synth") -> InstanceSource:
    """A calibrated synthetic request stream (the azure-like generator),
    sized for benchmarks: ``n_items`` VMs => ``2 n_items`` events."""
    from ..data.traces import _one_instance
    return InstanceSource(_one_instance(seed, n_items, d, pm_cores,
                                        med_lifetime, sigma_lifetime, name))


def chunk_instance_events(times, kinds, items, chunk_events: int,
                          extras: Tuple[np.ndarray, ...] = ()):
    """Cut pre-materialized single-lane event arrays (any kinds, including
    MIGRATE) into PAD-padded fixed-geometry slices - the low-level chunking
    used by ``stream.replay.replay_chunked_events`` and the chunk-boundary
    tests.  Yields (times, kinds, items, extras, final) per chunk."""
    C = int(chunk_events)
    E = len(times)
    times = np.asarray(times, np.float32)
    kinds = np.asarray(kinds, np.int32)
    items = np.asarray(items, np.int32)
    nchunks = max(-(-E // C), 1)
    for s in range(0, nchunks * C, C):
        e = min(s + C, E)
        t = np.zeros(C, np.float32)
        k = np.full(C, PAD_KIND, np.int32)
        i = np.zeros(C, np.int32)
        t[:e - s] = times[s:e]
        k[:e - s] = kinds[s:e]
        i[:e - s] = items[s:e]
        ex = []
        for x in extras:
            xa = np.asarray(x)
            pad = np.zeros(C, xa.dtype)
            pad[:e - s] = xa[s:e]
            if e > s:               # PAD events carry the running value
                pad[e - s:] = xa[e - 1]
            ex.append(pad)
        yield t, k, i, tuple(ex), e >= E
