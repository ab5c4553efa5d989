"""Checkpoint/resume for long replays: atomic snapshots of the scan carry.

The batched replay is a ``lax.scan`` over the event axis; its carry at any
event boundary is the complete replay state (slot loads, category state,
running usage - see ``core.jaxsim._replay_batch``).  ``checkpointed_replay``
drives the same scan in fixed-shape *segments* of ``every_events`` events
(padding the tail with PAD no-op events, rounded to a ``block_events``
multiple so the megakernel path segments identically), snapshotting the
carry between segments.  A killed run resumes from the last snapshot and
produces bit-identical usage/bins - the segments replay the identical
event stream with the identical carry.

Two correctness subtleties the segmentation must respect:

  * RCP's running distinct-category count is a cumsum over the *whole*
    event axis (``jaxsim._category_setup``); it is computed once here on
    the full padded stream (``jaxsim.replay_event_extras``) and sliced per
    segment - recomputing it inside a segment would restart the count and
    change decisions.
  * Segments share one jit trace (fixed event shape, carry passed in as a
    traced pytree); only the first segment (no carry yet) traces
    separately.

Snapshot format: one ``.npz`` written to a temp file, fsynced, then
atomically renamed; holds the carry leaves, a JSON header (pytree
structure + run metadata) and a content checksum.  Loading verifies the
checksum and that the metadata matches the *current* run (policy, padded
geometry, backend, a digest of the input arrays) - a stale or torn
snapshot is quarantined to a ``.corrupt`` sidecar and ignored, never
trusted.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from functools import partial
from typing import Optional, Tuple

import jax
import numpy as np

from .. import obs
from ..kernels.fitscore import SELECT_LAYOUT
from . import faults

# ------------------------------------------------- pytree (de)serialization
# Scan carries are nests of dict/tuple over arrays; encode the structure as
# JSON instead of pickling treedefs, so snapshots stay inspectable and
# loadable across jax versions.


def _pack(obj, leaves):
    if obj is None:
        return {"t": "none"}
    if isinstance(obj, dict):
        keys = sorted(obj)
        return {"t": "dict", "k": keys,
                "v": [_pack(obj[k], leaves) for k in keys]}
    if isinstance(obj, (tuple, list)):
        return {"t": "tuple" if isinstance(obj, tuple) else "list",
                "v": [_pack(x, leaves) for x in obj]}
    leaves.append(np.asarray(obj))
    return {"t": "leaf", "i": len(leaves) - 1}


def _unpack(node, leaves):
    t = node["t"]
    if t == "none":
        return None
    if t == "dict":
        return {k: _unpack(v, leaves)
                for k, v in zip(node["k"], node["v"])}
    if t in ("tuple", "list"):
        seq = [_unpack(v, leaves) for v in node["v"]]
        return tuple(seq) if t == "tuple" else seq
    return leaves[node["i"]]


def _checksum(structure: dict, leaves) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(json.dumps(structure, sort_keys=True).encode())
    for a in leaves:
        h.update(str((a.shape, str(a.dtype))).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def save_checkpoint(path: str, carry, meta: dict) -> str:
    """Atomically snapshot a carry pytree: tmp + fsync + rename, with a
    content checksum in the header."""
    leaves = []
    structure = _pack(carry, leaves)
    header = {"meta": meta, "structure": structure,
              "checksum": _checksum(structure, leaves)}
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, __header__=np.array(json.dumps(header)),
                     **{f"leaf_{i}": a for i, a in enumerate(leaves)})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    faults.fire("ckpt.save", path=path)
    return path


def load_checkpoint(path: str, expect_meta: Optional[dict] = None):
    """Load a snapshot; returns ``(carry, meta)`` or None.

    None means "start from scratch": missing file, torn/corrupt file
    (checksum or parse failure - quarantined to ``path.corrupt``), or
    metadata not matching ``expect_meta`` (a snapshot from a different
    run/geometry; left in place, counted as stale)."""
    if not os.path.exists(path):
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            header = json.loads(str(z["__header__"].item()))
            leaves = [z[f"leaf_{i}"] for i in
                      range(len(z.files) - 1)]
        if header["checksum"] != _checksum(header["structure"], leaves):
            raise ValueError("checkpoint checksum mismatch")
    except Exception as e:   # torn write, bad zip, bad json: quarantine
        side = path + ".corrupt"
        os.replace(path, side)
        obs.counter_add("resilience.ckpt_corrupt")
        obs.instant("resilience.ckpt_corrupt", path=path,
                    error=str(e)[:200])
        return None
    meta = header["meta"]
    if expect_meta is not None and \
            any(meta.get(k) != v for k, v in expect_meta.items()):
        obs.counter_add("resilience.ckpt_stale")
        return None
    return _unpack(header["structure"], leaves), meta


# --------------------------------------------------------- segmented replay

@dataclasses.dataclass
class ReplayCheckpointer:
    """Where/how often to snapshot a segmented replay.

    ``every_events`` is the segment length (rounded up to a
    ``block_events`` multiple); ``resume=False`` ignores existing
    snapshots (they are overwritten); ``keep=True`` leaves the final
    snapshot on disk after a completed run (default: deleted - a finished
    replay needs no resume point)."""

    root: str
    every_events: int = 2048
    resume: bool = True
    keep: bool = False

    def path_for(self, key: str) -> str:
        safe = "".join(c if c.isalnum() or c in "._-" else "-"
                       for c in key)
        return os.path.join(self.root, f"ckpt_{safe}.npz")


@partial(jax.jit, static_argnames=("policy", "max_bins", "backend",
                                   "block_events", "migrate"))
def _segment(sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps,
             n_items, ev_extra, carry0, *, policy: str, max_bins: int,
             backend: str, block_events: int, migrate: bool = False):
    from ..core.jaxsim import _replay_batch
    return _replay_batch(
        sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps, n_items,
        policy=policy, max_bins=max_bins, backend=backend,
        block_events=block_events, carry0=carry0, return_carry=True,
        ev_extra=ev_extra, migrate=migrate)


def _input_digest(arrays, policy, max_bins, backend, block_events,
                  seg: int, migrate: bool = False) -> str:
    h = hashlib.blake2b(digest_size=8)
    # the layout tag: a snapshot of a carry in another per-event layout is
    # stale, recomputed instead of resumed
    h.update(f"{policy}|{max_bins}|{backend}|{block_events}|{seg}"
             f"|mig{int(migrate)}|{SELECT_LAYOUT}".encode())
    for a in arrays:
        if a is None:
            h.update(b"|none")
            continue
        a = np.asarray(a)
        h.update(str((a.shape, str(a.dtype))).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def checkpointed_replay(arrays, *, policy: str, max_bins: int,
                        backend: str, block_events: int,
                        ckpt: ReplayCheckpointer, key: str,
                        migrate: bool = False):
    """Replay flattened lanes in checkpointed segments.

    ``arrays`` is the runner's flattened-lane tuple (sizes, times, kinds,
    items, pdeps (L, n_max), dmask, arrivals, rdeps, n_items).  Returns
    (usage (L,), opened (L,), placements (L, n_max), overflow (L,)) -
    bit-identical to the unsegmented replay (tests/test_resilience.py
    asserts it per policy family).  Single-device by construction; the
    runner's ladder handles sharding.  ``migrate=True`` compiles the
    MIGRATE event branch in (streams carrying consolidation events);
    the flag is part of the snapshot digest so a resume never mixes
    graphs."""
    from ..core.jaxsim import PAD_KIND, replay_event_extras
    sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps, n_items = \
        arrays
    times = np.asarray(times)
    kinds = np.asarray(kinds)
    items = np.asarray(items)
    L, E = times.shape
    T = max(int(block_events), 1)
    seg = max(int(ckpt.every_events), T)
    seg = -(-seg // T) * T                 # block-multiple segments
    nseg = max(-(-E // seg), 1)
    pad = nseg * seg - E
    if pad:
        # PAD events are no-ops (the carry passes through), so padding the
        # tail up to a segment multiple never changes decisions
        times = np.concatenate(
            [times, np.zeros((L, pad), times.dtype)], axis=1)
        kinds = np.concatenate(
            [kinds, np.full((L, pad), PAD_KIND, kinds.dtype)], axis=1)
        items = np.concatenate(
            [items, np.zeros((L, pad), items.dtype)], axis=1)
    extras = replay_event_extras(policy, sizes, pdeps, dmask, arrivals,
                                 rdeps, n_items, times, kinds, items)
    digest = _input_digest(arrays, policy, max_bins, backend, block_events,
                           seg, migrate)
    path = ckpt.path_for(key)
    start, carry = 0, None
    if ckpt.resume:
        loaded = load_checkpoint(path, {"digest": digest})
        if loaded is not None:
            carry, meta = loaded
            carry = jax.tree.map(lambda a: a, carry)   # plain np leaves
            start = int(meta["next_seg"])
            obs.counter_add("resilience.ckpt_resume")
            obs.instant("resilience.ckpt_resume", key=key, seg=start)
    out = None
    for s in range(start, nseg):
        faults.fire("ckpt.segment")
        lo, hi = s * seg, (s + 1) * seg
        usage, opened, placements, overflow, carry = _segment(
            sizes, times[:, lo:hi], kinds[:, lo:hi], items[:, lo:hi],
            pdeps, dmask, arrivals, rdeps, n_items,
            tuple(np.asarray(x)[:, lo:hi] for x in extras), carry,
            policy=policy, max_bins=max_bins, backend=backend,
            block_events=block_events, migrate=migrate)
        out = (usage, opened, placements, overflow)
        if s + 1 < nseg:
            # snapshot BETWEEN segments: the carry is the full replay
            # state, so resume needs nothing else
            save_checkpoint(
                path, jax.tree.map(np.asarray, carry),
                {"digest": digest, "next_seg": s + 1, "policy": policy,
                 "max_bins": int(max_bins), "backend": backend,
                 "block_events": int(block_events)})
            obs.counter_add("resilience.ckpt_save")
    if not ckpt.keep and os.path.exists(path):
        os.unlink(path)
    return out


# --------------------------------------------------------- streamed replay

@dataclasses.dataclass
class StreamCheckpointer:
    """Chunk-boundary snapshots for ``repro.stream.replay_stream``.

    The streamed replay's complete state at a chunk boundary is (carry,
    row pool, chunk index): the host-side chunk builder is deterministic,
    so a resumed run rebuilds it by fast-forwarding the request stream to
    the snapshot's chunk - no event arrays are ever persisted.  Snapshots
    reuse the atomic/checksummed ``save_checkpoint`` format; the digest
    key covers the source fingerprint and the full replay config (policy,
    pool size, backend, block/chunk geometry), so a snapshot from a
    different stream or geometry is stale, never trusted.

    ``every_chunks`` is the snapshot cadence (each save fences the device
    pipeline - the double-buffered overlap resumes on the next chunk);
    ``keep=True`` leaves the last snapshot after a completed run."""

    root: str
    every_chunks: int = 8
    resume: bool = True
    keep: bool = False

    def key(self, fingerprint: str, *, policy: str, max_bins: int,
            backend: str, block_events: int, chunk_events: int) -> str:
        h = hashlib.blake2b(digest_size=8)
        h.update(f"{fingerprint}|{policy}|{max_bins}|{backend}"
                 f"|{block_events}|{chunk_events}|{SELECT_LAYOUT}".encode())
        return f"{policy}-{h.hexdigest()}"

    def path_for(self, key: str) -> str:
        safe = "".join(c if c.isalnum() or c in "._-" else "-"
                       for c in key)
        return os.path.join(self.root, f"stream_{safe}.npz")

    def load(self, key: str):
        """(carry, pool, chunks_done) from a matching snapshot, or None."""
        if not self.resume:
            return None
        loaded = load_checkpoint(self.path_for(key), {"digest": key})
        if loaded is None:
            return None
        state, meta = loaded
        import jax.numpy as jnp
        state = jax.tree.map(jnp.asarray, state)
        obs.counter_add("resilience.stream_ckpt_resume")
        obs.instant("resilience.stream_ckpt_resume", key=key,
                    chunks=int(meta["chunks"]))
        return state["carry"], state["pool"], int(meta["chunks"])

    def maybe_save(self, key: str, carry, pool, chunks: int, *,
                   final: bool) -> None:
        path = self.path_for(key)
        if final:
            if not self.keep and os.path.exists(path):
                os.unlink(path)
            return
        if chunks % max(int(self.every_chunks), 1):
            return
        state = jax.tree.map(np.asarray, {"carry": carry, "pool": pool})
        save_checkpoint(path, state, {"digest": key, "chunks": int(chunks)})
        obs.counter_add("resilience.stream_ckpt_save")
