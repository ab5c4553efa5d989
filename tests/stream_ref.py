"""Plain reference for the streamed chunk builder (``stream.ChunkedWorkload``).

One record at a time: a Python min-heap of pending departures keyed
``(departure_time, item_seq)``, drained before every arrival whose time is
at or past them, and scalar writes of each event into the open chunk.  It
defines the chunks the block-vectorised builder must yield, field for field
and chunk for chunk.  No program code imports it.
"""
from __future__ import annotations

import heapq
from typing import Iterator, Optional

import numpy as np

from repro.core.algorithms.learned import geo_class
from repro.core.jaxsim import policy_spec
from repro.kernels.fitscore import ARRIVAL_KIND, DEPARTURE_KIND, KCAT, PAD_KIND
from repro.stream.events import POOL_SENTINEL, EventChunk


class RefChunkedWorkload:
    """Same constructor and queries as ``ChunkedWorkload``."""

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
            assert n >= 0
            item_rows = max(int(n), 1)
            grow = False
        self.item_rows = max(int(item_rows), 1)
        self.grow = bool(grow)
        self.d = source.meta().d
        self._row_seq = {}          # pool row -> global item seq, alive only
        self._seq_count = 0

    def chunks(self) -> Iterator[EventChunk]:
        C, d = self.chunk_events, self.d
        rcp = self.spec.family == "rcp"
        free: list = []             # allocatable rows (min-heap)
        next_fresh = 0
        heap: list = []             # (dep_time f64, seq, row) pending deps
        seen_cats = [False] * KCAT
        xcount = 0
        last_arr = -np.inf

        ev_t = np.zeros(C, np.float32)
        ev_k = np.full(C, PAD_KIND, np.int32)
        ev_i = np.zeros(C, np.int32)
        ev_x = np.zeros(C, np.int32)
        upd_idx = np.full(C, POOL_SENTINEL, np.int32)
        upd_size = np.zeros((C, d), np.float32)
        upd_arr = np.zeros(C, np.float32)
        upd_rdep = np.zeros(C, np.float32)
        upd_pdep = np.zeros(C, np.float32)
        freed: list = []
        freed_seqs: list = []
        fill = 0
        nupd = 0

        def cut(final: bool) -> EventChunk:
            nonlocal fill, nupd
            fr = np.full(C, POOL_SENTINEL, np.int32)
            fr[:len(freed)] = freed
            fseq = np.full(C, -1, np.int64)
            fseq[:len(freed_seqs)] = freed_seqs
            chunk = EventChunk(
                ev_t.copy(), ev_k.copy(), ev_i.copy(), fill,
                upd_idx.copy(), upd_size.copy(), upd_arr.copy(),
                upd_rdep.copy(), upd_pdep.copy(),
                (ev_x.copy(),) if rcp else (),
                fr, fseq, self.item_rows, final)
            for r in freed:
                heapq.heappush(free, int(r))
            freed.clear()
            freed_seqs.clear()
            ev_t[:] = 0.0
            ev_k[:] = PAD_KIND
            ev_i[:] = 0
            ev_x[:] = xcount
            upd_idx[:] = POOL_SENTINEL
            upd_size[:] = 0.0
            upd_arr[:] = upd_rdep[:] = upd_pdep[:] = 0.0
            fill = nupd = 0
            return chunk

        def put(t: float, kind: int, row: int) -> Optional[EventChunk]:
            nonlocal fill
            ev_t[fill] = np.float32(t)
            ev_k[fill] = kind
            ev_i[fill] = row
            ev_x[fill] = xcount
            fill += 1
            return cut(False) if fill == C else None

        def alloc(seq: int) -> int:
            nonlocal next_fresh
            if not self.identity and free:
                return heapq.heappop(free)
            if next_fresh >= self.item_rows:
                if not self.grow:
                    raise RuntimeError(
                        f"item-row pool exhausted ({self.item_rows} rows) "
                        f"at request #{seq} with grow=False; pass a larger "
                        "item_rows or grow=True")
                self.item_rows *= 2
            row = next_fresh
            next_fresh += 1
            return row

        for size, arr, rdep, pdep in self.source.records():
            if arr < last_arr:
                raise ValueError(
                    f"stream not arrival-sorted: {arr} after {last_arr}")
            assert rdep > arr, f"departure {rdep} <= arrival {arr}"
            last_arr = arr
            while heap and heap[0][0] <= arr:
                dt, dseq, drow = heapq.heappop(heap)
                freed.append(drow)
                freed_seqs.append(dseq)
                del self._row_seq[drow]
                out = put(dt, DEPARTURE_KIND, drow)
                if out is not None:
                    yield out
            seq = self._seq_count
            self._seq_count += 1
            row = seq if self.identity else alloc(seq)
            if rcp:
                pdur = np.float32(pdep) - np.float32(arr)
                cat = int(np.clip(geo_class(max(pdur, np.float32(0.0))),
                                  0, KCAT - 1))
                if not seen_cats[cat]:
                    seen_cats[cat] = True
                    xcount += 1
            self._row_seq[row] = seq
            upd_idx[nupd] = row
            upd_size[nupd] = np.asarray(size, np.float32)[:d]
            upd_arr[nupd] = np.float32(arr)
            upd_rdep[nupd] = np.float32(rdep)
            upd_pdep[nupd] = np.float32(pdep)
            nupd += 1
            heapq.heappush(heap, (float(rdep), seq, row))
            out = put(arr, ARRIVAL_KIND, row)
            if out is not None:
                yield out
        while heap:
            dt, dseq, drow = heapq.heappop(heap)
            freed.append(drow)
            freed_seqs.append(dseq)
            del self._row_seq[drow]
            out = put(dt, DEPARTURE_KIND, drow)
            if out is not None:
                yield out
        yield cut(True)

    @property
    def n_items(self) -> int:
        return self._seq_count

    def live_rows(self):
        return dict(self._row_seq)
