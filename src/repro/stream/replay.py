"""The streamed replay driver: chunked device work on a carried state.

``replay_stream`` drives one policy over one request stream in
fixed-geometry chunks (see ``stream.events``): every chunk is a single
jitted step that (1) scatters the chunk's newly arrived items into the
device row pool, (2) replays the C events through
``core.jaxsim._replay_batch`` with the carry threaded in and out
(``carry0`` / ``return_carry`` - the checkpoint-segment machinery), and
(3) harvests the placements of rows the chunk freed, before they are
recycled.  Usage / opened-bins / overflow accumulate inside the carry, so
the last chunk's outputs are the full-run totals, bit-identical to the
in-memory replay of the same event stream (tests/test_stream.py).  On the
kernel backends step (2) runs on the event-blocked megakernel whenever its
packed carry fits VMEM (``jaxsim.replay_block_events``): one lane has no
lanes to batch, and the per-event scan pays some fifty small device
operations an event.

Staging is double-buffered: with ``prefetch >= 1`` the host builds and
``device_put`` s up to that many chunks ahead while the device replays the
current one, and nothing fences until the final resolve - jax's async
dispatch overlaps host merge/CSV work with device compute exactly as the
serving front end's block placement does.  ``prefetch=0`` is the
synchronous mode (fence after every chunk), which the tests replay
against the prefetching one.

Recorded spans mark the staging boundaries: ``stream.init`` (builder,
carry and pool of a pass; ``loads_bytes`` is the carried slot loads'
size, which names the layout), ``stream.build`` (one chunk's merge and
build; ``records`` is how many source records it pulled, a whole block or
none), ``stream.put`` (its transfer), ``stream.step`` (its dispatch) and
``stream.fence`` (waiting on the device); ``stream.replay`` carries the
events and chunks replayed and the ``block_events`` of the last pass (0:
per-event).  One span per chunk, never one per event.  The counter
``stream.blocked_chunks`` counts the chunks run on the megakernel.

Memory is O(pool): the carry, the row pool and at most ``prefetch + 1``
staged chunks - independent of trace length.  ``peak_device_bytes``
reports the accounted maximum.  Overflow keeps the in-memory escalation
ladder: the stream is replayed again from the source with a doubled slot
pool (sources are re-iterable factories).
"""
from __future__ import annotations

import dataclasses
import math
from collections import deque
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.jaxsim import (CapacityError, MAX_BINS_CAP, _replay_batch,
                           grow_live_items, grow_max_bins, policy_spec,
                           replay_block_events, replay_init_carry,
                           replay_loads_shape, resolve_backend)
from ..kernels import fitscore as _fk
from .events import ChunkedWorkload, InstanceSource, chunk_instance_events


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Outcome of one streamed replay (single lane)."""
    usage: float
    opened: int
    overflow: bool
    max_bins: int
    n_items: int
    n_events: int
    n_chunks: int
    item_rows: int
    peak_device_bytes: int
    placements: Optional[np.ndarray] = None


def _pool0(item_rows: int, d: int):
    f32 = jnp.float32
    return {"sizes": jnp.zeros((1, item_rows, d), f32),
            "arrivals": jnp.zeros((1, item_rows), f32),
            "rdeps": jnp.zeros((1, item_rows), f32),
            "pdeps": jnp.zeros((1, item_rows), f32)}


def _pool_full(source: InstanceSource):
    """Identity (hybrid) mode: the whole item table up front."""
    f32 = jnp.float32
    sizes, arrivals, rdeps, pdeps = source.full_arrays()
    return {"sizes": jnp.asarray(sizes, f32)[None],
            "arrivals": jnp.asarray(arrivals, f32)[None],
            "rdeps": jnp.asarray(rdeps, f32)[None],
            "pdeps": jnp.asarray(pdeps, f32)[None]}


def _grow_pool(pool, item_rows: int):
    n = pool["sizes"].shape[1]
    if item_rows <= n:
        return pool
    pad = item_rows - n
    return {k: jnp.concatenate(
        [v, jnp.zeros((1, pad) + v.shape[2:], v.dtype)], axis=1)
        for k, v in pool.items()}


def _grow_carry(carry, item_rows: int):
    """Pad the carried state's item axis; fresh rows are virgin (-1
    placements, zero category state), so decisions are unchanged - new
    rows are only referenced once the builder assigns them."""
    if isinstance(carry, dict):            # packed kernel carry
        return grow_live_items(carry, item_rows)
    core, cat = carry
    n = core[7].shape[1]
    if item_rows <= n:
        return carry
    pad = item_rows - n
    core = core[:7] + (jnp.concatenate(
        [core[7], jnp.full((1, pad), -1, jnp.int32)], axis=1),) + core[8:]
    cat = dict(cat)
    if "loc" in cat:                       # RCP's per-item slot memo
        cat["loc"] = jnp.concatenate(
            [cat["loc"], jnp.zeros((1, pad), jnp.int32)], axis=1)
    assert "agg" not in cat, "hybrid never grows (identity mode)"
    return (core, cat)


def _carry_placements(carry):
    if isinstance(carry, dict):
        return carry["itemi"][:, :, _fk.ITEMI_PLACE]
    return carry[0][7]


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("policy", "max_bins", "backend", "block_events",
                          "migrate", "harvest"))
def _chunk_step(carry, pool, times, kinds, items, upd_idx, upd_size,
                upd_arr, upd_rdep, upd_pdep, extras, freed, *, policy: str,
                max_bins: int, backend: str, block_events: int,
                migrate: bool, harvest: bool):
    """One chunk of device work: pool scatter -> replay -> harvest.

    Carry and pool are donated (reused in place chunk over chunk); the
    ``POOL_SENTINEL`` padding of ``upd_idx`` / ``freed`` is dropped /
    filled, so every chunk shares this one trace."""
    pool = dict(pool)
    pool["sizes"] = pool["sizes"].at[0, upd_idx].set(upd_size, mode="drop")
    pool["arrivals"] = pool["arrivals"].at[0, upd_idx].set(
        upd_arr, mode="drop")
    pool["rdeps"] = pool["rdeps"].at[0, upd_idx].set(upd_rdep, mode="drop")
    pool["pdeps"] = pool["pdeps"].at[0, upd_idx].set(upd_pdep, mode="drop")
    item_rows = pool["sizes"].shape[1]
    n1 = jnp.full((1,), item_rows, jnp.int32)
    usage, opened, placements, overflow, carry = _replay_batch(
        pool["sizes"], times[None], kinds[None], items[None],
        pool["pdeps"], None, pool["arrivals"], pool["rdeps"], n1,
        policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, carry0=carry, return_carry=True,
        ev_extra=tuple(x[None] for x in extras) if extras else None,
        migrate=migrate)
    freed_place = jnp.take(placements[0], freed, mode="fill",
                           fill_value=-1) if harvest else None
    return carry, pool, usage[0], opened[0], overflow[0], freed_place


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


class _OutgrewBlock(Exception):
    """The row pool grew past what the megakernel's VMEM holds: the pass
    restarts on the per-event path (events and chunks it replayed)."""

    def __init__(self, events: int, chunks: int):
        super().__init__(events, chunks)
        self.events, self.chunks = events, chunks


def _replay_once(source, policy, *, chunk_events, item_rows, max_bins,
                 backend, block_events, prefetch, grow_pool,
                 collect_placements, checkpointer):
    """One pass over the source at ``max_bins`` slots.  ``block_events``
    None lets ``replay_block_events`` pick the path from the pass's
    geometry; returns (result, the ``block_events`` used)."""
    with obs.span("stream.init", max_bins=max_bins) as sp:
        wl = ChunkedWorkload(source, policy, chunk_events=chunk_events,
                             item_rows=item_rows, grow=grow_pool)
        d = wl.d
        rows = wl.item_rows
        sp.set(item_rows=rows)
        pick = partial(replay_block_events, policy, backend=backend, L=1,
                       max_bins=max_bins, d=d, chunk_events=chunk_events)
        auto = block_events is None
        if auto:
            block_events = pick(item_rows=rows)
        carry = replay_init_carry(policy, max_bins, d, rows, L=1,
                                  backend=backend, block_events=block_events)
        sp.set(loads_bytes=4 * math.prod(replay_loads_shape(
            1, max_bins, d, backend=backend, block_events=block_events)))
        pool = _pool_full(source) if wl.identity else _pool0(rows, d)
    gen = wl.chunks()

    def build():
        with obs.span("stream.build") as sp:
            pulled = wl.records_pulled
            ch = next(gen)
            sp.set(events=ch.n_events, records=wl.records_pulled - pulled)
        return ch

    resumed_chunks = 0
    ckpt_key = None
    if checkpointer is not None:
        assert not collect_placements, \
            "checkpoint/resume discards freed-row placement logs; " \
            "collect only on un-checkpointed runs"
        ckpt_key = checkpointer.key(
            source.meta().fingerprint, policy=policy, max_bins=max_bins,
            backend=backend, block_events=block_events,
            chunk_events=chunk_events)
        state = checkpointer.load(ckpt_key)
        if state is not None:
            carry, pool, resumed_chunks = state
            rows = pool["sizes"].shape[1]
            for _ in range(resumed_chunks):   # host fast-forward (cheap,
                build()                       # deterministic builder)

    depth = max(int(prefetch), 0)
    staged: deque = deque()
    harvest = []                # (freed_seqs, freed_place) per chunk
    last = None
    nchunks = resumed_chunks
    nevents = 0
    peak = 0
    done = False
    while True:
        while not done and len(staged) <= depth:
            ch = build()
            done = ch.final
            with obs.span("stream.put"):
                dev = jax.device_put((ch.times, ch.kinds, ch.items,
                                      ch.upd_idx, ch.upd_size,
                                      ch.upd_arrival, ch.upd_rdep,
                                      ch.upd_pdep, ch.extras, ch.freed))
            staged.append((ch, dev))
            peak = max(peak, _nbytes(carry) + _nbytes(pool) +
                       sum(_nbytes(s[1]) for s in staged))
        if not staged:
            break
        ch, dev = staged.popleft()
        with obs.span("stream.step"):
            if ch.item_rows > rows:
                # the builder outgrew the pool: pad pool + carry (one retrace)
                obs.counter_add("stream.pool_growths")
                rows = ch.item_rows
                if auto and block_events and not pick(item_rows=rows):
                    raise _OutgrewBlock(nevents, nchunks - resumed_chunks)
                pool = _grow_pool(pool, rows)
                carry = _grow_carry(carry, rows)
            carry, pool, usage, opened, overflow, fp = _chunk_step(
                carry, pool, *dev, policy=policy, max_bins=max_bins,
                backend=backend, block_events=block_events, migrate=False,
                harvest=collect_placements)
        if block_events:
            obs.counter_add("stream.blocked_chunks")
        if collect_placements:
            harvest.append((ch.freed_seqs, fp))
        last = (usage, opened, overflow)
        nchunks += 1
        nevents += ch.n_events
        if depth == 0:
            with obs.span("stream.fence"):  # synchronous reference mode
                jax.block_until_ready(carry)
        if checkpointer is not None:
            checkpointer.maybe_save(ckpt_key, carry, pool, nchunks,
                                    final=ch.final)

    with obs.span("stream.fence"):
        usage, opened, overflow = jax.block_until_ready(last)
    placements = None
    if collect_placements:
        placements = np.full(wl.n_items, -1, np.int32)
        for seqs, fp in harvest:
            fp = np.asarray(fp)
            m = seqs >= 0
            placements[seqs[m]] = fp[m]
        live = wl.live_rows()
        if live:                   # items still alive at stream end
            final = np.asarray(_carry_placements(carry))[0]
            for row, seq in live.items():
                placements[seq] = final[row]
    return StreamResult(float(usage), int(opened), bool(overflow),
                        max_bins, wl.n_items, 2 * wl.n_items, nchunks,
                        rows, int(peak), placements), block_events


def replay_stream(source, policy: str, *, chunk_events: int = 2048,
                  item_rows: int = 256, max_bins: int = 64,
                  max_bins_cap: int = MAX_BINS_CAP, auto_grow: bool = True,
                  backend: Optional[str] = None,
                  block_events: Optional[int] = None, prefetch: int = 1,
                  grow_pool: bool = True, collect_placements: bool = False,
                  checkpointer=None) -> StreamResult:
    """Replay one request stream under one policy in bounded memory.

    Bit-identical to ``jaxsim.simulate`` on the materialized instance
    (same events, same carry evolution, same escalation ladder); peak
    memory O(item-row pool + slot pool + staged chunks).  See the module
    docstring for staging/prefetch semantics.

    ``block_events`` None (the default) picks the device path per pass
    from the geometry (``jaxsim.replay_block_events``): the event-blocked
    megakernel on kernel backends while the packed carry fits VMEM, else
    the per-event scan; a pass whose pool outgrows VMEM restarts on the
    per-event scan, which the rest of the call keeps.  An explicit value
    (0: per-event) is used as given."""
    backend = resolve_backend(backend)
    policy_spec(policy)            # validate before any device work
    events = chunks = 0
    with obs.span("stream.replay", cat="stream", policy=policy,
                  backend=backend, chunk_events=int(chunk_events)) as sp:
        while True:
            try:
                res, used = _replay_once(
                    source, policy, chunk_events=chunk_events,
                    item_rows=item_rows, max_bins=max_bins,
                    backend=backend, block_events=block_events,
                    prefetch=prefetch, grow_pool=grow_pool,
                    collect_placements=collect_placements,
                    checkpointer=checkpointer)
            except _OutgrewBlock as e:
                events += e.events
                chunks += e.chunks
                block_events = 0
                continue
            events += res.n_events
            chunks += res.n_chunks
            if not res.overflow or not auto_grow:
                sp.set(events=events, chunks=chunks, block_events=used)
                return res
            if max_bins >= max_bins_cap:
                raise CapacityError(
                    f"slot pool exhausted streaming with {policy!r}: "
                    f"still overflowing at max_bins={max_bins} "
                    f"(cap {max_bins_cap})", policy=policy,
                    max_bins=max_bins)
            obs.counter_add("stream.overflow_rungs")
            max_bins = grow_max_bins(max_bins, max_bins_cap)


def replay_chunked_events(sizes, times, kinds, items, pdeps, arrivals,
                          rdeps, *, policy: str, chunk_events: int,
                          max_bins: int, backend: str = "jnp",
                          block_events: int = 0, migrate: bool = False,
                          ev_extra=None):
    """Replay pre-materialized single-lane event arrays (any kinds,
    MIGRATE included) in fixed-geometry chunks with the carry threaded
    across boundaries - the minimal chunked path for the chunk-boundary
    equivalence tests, sharing ``_chunk_step``'s scatter-free core.

    ``ev_extra`` (full-event-axis tuple, e.g. ``replay_event_extras``) is
    sliced per chunk exactly as the checkpointed replay slices segments.
    Returns (usage, opened, placements, overflow) like ``_replay_batch``
    on a single lane."""
    n_max, d = np.asarray(sizes).shape
    carry = replay_init_carry(policy, max_bins, d, n_max, L=1,
                              backend=backend, block_events=block_events)
    pool = {"sizes": jnp.asarray(sizes, jnp.float32)[None],
            "arrivals": jnp.asarray(arrivals, jnp.float32)[None],
            "rdeps": jnp.asarray(rdeps, jnp.float32)[None],
            "pdeps": jnp.asarray(pdeps, jnp.float32)[None]}
    extras = tuple(np.asarray(x)[0] if np.asarray(x).ndim == 2 else
                   np.asarray(x) for x in (ev_extra or ()))
    sent = np.full(1, 2 ** 30, np.int32)
    no_upd = (sent, np.zeros((1, d), np.float32), np.zeros(1, np.float32),
              np.zeros(1, np.float32), np.zeros(1, np.float32))
    out = None
    for t, k, i, ex, final in chunk_instance_events(
            times, kinds, items, chunk_events, extras):
        carry, pool, usage, opened, overflow, _ = _chunk_step(
            carry, pool, t, k, i, *no_upd, ex, sent, policy=policy,
            max_bins=max_bins, backend=backend, block_events=block_events,
            migrate=migrate, harvest=False)
        out = (usage, opened, overflow)
    usage, opened, overflow = out
    placements = _carry_placements(carry)[0]
    return (np.asarray(usage), np.asarray(opened), np.asarray(placements),
            np.asarray(overflow))
