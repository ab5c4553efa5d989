"""Jit'd public wrappers with backend dispatch for every Pallas kernel.

On TPU backends the Pallas kernels run natively; elsewhere the pure-jnp
references (ref.py) run so the whole framework works identically on CPU
(dry-run, tests).  ``impl="pallas_interpret"`` forces the kernel body in
interpret mode (the correctness harness used by tests/test_kernels.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .fitscore import IBIG
from .fitscore import fitscore as _fitscore_pallas
from .flash_attention import flash_attention as _flash_pallas
from .rwkv6_scan import rwkv6_chunked as _rwkv6_pallas
from ..resilience import faults


def _use_pallas(impl: str) -> bool:
    if impl == "pallas":
        return True
    if impl == "auto":
        return jax.default_backend() == "tpu"
    return False


def _interpret(impl: str) -> bool:
    """Whether the event-blocked replay kernel runs in interpret mode: only
    when asked (``"pallas_interpret"``) or under ``"auto"`` off-TPU (the
    CPU test harness).  That kernel has no jnp twin, so ``"jnp"`` is
    refused rather than quietly interpreted."""
    if impl == "pallas_interpret":
        return True
    if impl == "pallas":
        return False
    if impl == "auto":
        return jax.default_backend() != "tpu"
    raise ValueError(
        f"impl={impl!r}: the blocked replay kernel has no jnp twin; use "
        "'pallas', 'pallas_interpret' or 'auto'")


def resolved_select_impl(impl: str, block: bool = False) -> str:
    """The engine that will *actually* serve a ``fitscore_select`` /
    ``fitscore_select_block`` call with this ``impl`` argument: "pallas"
    (native kernel), "pallas_interpret" (kernel body interpreted) or "jnp"
    (the ``_select_slot`` twin).  ``impl="auto"`` resolves to the native
    kernel on TPU; off-TPU the per-event select runs its jnp twin and the
    blocked select (no jnp twin) the kernel in interpret mode.  The
    resolved name tags the serving scheduler's spans and ``obs`` counters,
    so a run can prove which engine served it."""
    if block:
        return "pallas_interpret" if _interpret(impl) else "pallas"
    if _use_pallas(impl):
        return "pallas"
    if impl == "pallas_interpret":
        return "pallas_interpret"
    return "jnp"


@partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, *, causal=True, window=0, impl="auto"):
    if _use_pallas(impl):
        return _flash_pallas(q, k, v, causal=causal, window=window)
    if impl == "pallas_interpret":
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             interpret=True)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("impl",))
def decode_attention(q, k, v, kv_len, *, impl="auto"):
    if _use_pallas(impl):
        return _decode_pallas(q, k, v, kv_len)
    if impl == "pallas_interpret":
        return _decode_pallas(q, k, v, kv_len, interpret=True)
    return ref.decode_attention_ref(q, k, v, kv_len)


@partial(jax.jit, static_argnames=("chunk", "impl"))
def rwkv6(r, k, v, logw, u, *, chunk=16, impl="auto"):
    if _use_pallas(impl):
        return _rwkv6_pallas(r, k, v, logw, u, chunk=chunk)
    if impl == "pallas_interpret":
        return _rwkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    return ref.rwkv6_ref(r, k, v, jnp.clip(logw, -4.0, 0.0), u)


@partial(jax.jit, static_argnames=("norm", "impl"))
def fitscore(remaining, alive, item, open_seq=None, *, norm="linf",
             impl="auto"):
    """Scores + chosen bin.  Ties break by ``open_seq`` (opening order, the
    oracle's rule); ``open_seq=None`` means slot index == opening order."""
    if _use_pallas(impl):
        return _fitscore_pallas(remaining, alive, item, open_seq, norm=norm)
    if impl == "pallas_interpret":
        return _fitscore_pallas(remaining, alive, item, open_seq, norm=norm,
                                interpret=True)
    n = remaining.shape[0]
    if open_seq is None:
        open_seq = jnp.arange(n, dtype=jnp.int32)
    if norm == "first_fit":
        feasible = jnp.all(remaining - item[None, :] >= -1e-9, axis=1) & \
            (alive > 0)
        scores = jnp.where(feasible, open_seq.astype(jnp.float32), jnp.inf)
    else:
        scores, feasible = ref.fitscore_ref(remaining, alive > 0, item,
                                            norm=norm)
    tie = scores <= jnp.min(scores)
    best = jnp.argmin(jnp.where(tie, open_seq.astype(jnp.int32),
                                jnp.int32(IBIG)))
    best = jnp.where(jnp.isinf(scores).all(), -1, best)
    return scores, best.astype(jnp.int32)


def fitscore_select(loads, counts, alive, open_seq, access_seq, closes,
                    size, pdep, now, dmask=None, cmask=None, *, policy,
                    impl="auto"):
    """Host wrapper over the jitted select: crosses the ``kernel.select``
    fault seam, then dispatches.  The seam must sit *outside* the jit - a
    seam inside a traced body would fire once at trace time and never
    again (see ``resilience.faults``)."""
    faults.fire("kernel.select")
    return _fitscore_select_jit(
        loads, counts, alive, open_seq, access_seq, closes, size, pdep,
        now, dmask, cmask, policy=policy, impl=impl)


@partial(jax.jit, static_argnames=("policy", "impl"))
def _fitscore_select_jit(loads, counts, alive, open_seq, access_seq, closes,
                         size, pdep, now, dmask=None, cmask=None, *, policy,
                         impl="auto"):
    """Fused single-state placement decision over the full 8-policy family
    (``core.jaxsim.POLICIES``): loads (N,d), counts/alive/open_seq/
    access_seq/closes (N,), size (d,), pdep/now scalars.  ``cmask`` (N,)
    optionally restricts the decision to category-compatible slots (1 =
    eligible) - how the category-structured policies (CBD/CBDT, ...) route
    their First Fit stage through the same kernel.  Returns
    (slot, found, no_free); the serving scheduler's on-device select."""
    from ..core.jaxsim import _select_slot   # leaf-safe: jaxsim -> fitscore
    from .fitscore import fitscore_select_batch
    if dmask is None:
        dmask = jnp.ones_like(size)
    if _use_pallas(impl) or impl == "pallas_interpret":
        slot, found, no_free = fitscore_select_batch(
            loads[None], counts[None], alive[None], open_seq[None],
            access_seq[None], closes[None], size[None],
            jnp.asarray(pdep, jnp.float32).reshape(1),
            jnp.asarray(now, jnp.float32).reshape(1), dmask[None],
            None if cmask is None else cmask[None],
            policy=policy, interpret=(impl == "pallas_interpret"))
        return slot[0], found[0], no_free[0]
    return _select_slot(policy, loads, counts, alive, open_seq, access_seq,
                        closes, size, pdep, now, dmask, cmask)


def fitscore_select_block(loads, alive, open_seq, access_seq, closes, size,
                          pdep, now, cat=None, tags=None, *, policy, n, d,
                          impl="auto"):
    """Host wrapper over the jitted blocked select: crosses the
    ``kernel.select_block`` fault seam, then dispatches (seam outside the
    jit, same as ``fitscore_select``)."""
    faults.fire("kernel.select_block")
    return _fitscore_select_block_jit(
        loads, alive, open_seq, access_seq, closes, size, pdep, now, cat,
        tags, policy=policy, n=n, d=d, impl=impl)


def fitscore_replay_dispatch(carry, ev_i, ev_f, ev_size, dmask, *, policy,
                             n, d, impl="auto", migrate=False):
    """Host wrapper over the jitted block dispatch: crosses the
    ``kernel.dispatch_block`` fault seam, then dispatches (seam outside
    the jit, same as the other select wrappers).  ``migrate=True``
    compiles the MIGRATE branch in (consolidation drain blocks); plain
    arrival/departure blocks keep the exact non-migrating graph."""
    faults.fire("kernel.dispatch_block")
    return _fitscore_replay_dispatch_jit(
        carry, ev_i, ev_f, ev_size, dmask, policy=policy, n=n, d=d,
        impl=impl, migrate=migrate)


@partial(jax.jit, static_argnames=("policy", "n", "d", "impl", "migrate"))
def _fitscore_replay_dispatch_jit(carry, ev_i, ev_f, ev_size, dmask, *,
                                  policy, n, d, impl="auto",
                                  migrate=False):
    """One T-event block of a *live* replay: the serving front end's batch
    of pending arrivals (plus fired departures, plus ``PAD_KIND`` filler up
    to the fixed block geometry) replayed against a persistent single-lane
    carry (``core.jaxsim.make_live_carry``) by the event-blocked megakernel
    - the whole block placed in a single on-chip pass, carry aliased
    in -> out exactly as in the sweep scan.

    ``policy`` is any scan policy whose family has a live-carry form
    (score / cbd / cbdt / rcp / la / adaptive); the ``PolicySpec`` knobs
    resolve here so the dispatcher passes one name, not nine flags.  The
    jit cache is keyed on (policy, n, d, impl) and the event shapes, so a
    fixed set of T geometries keeps the trace count bounded
    (``dispatch_trace_count`` is the monitored invariant).  Returns the
    post-block carry; placements read back from
    ``itemi[..., ITEMI_PLACE]``, overflow from ``si[..., SI_OVERFLOW]``.
    """
    from ..core.jaxsim import _KERNEL_FAMILY, policy_spec   # leaf-safe
    from ..core.algorithms.learned import LA_BINARY_SPLIT
    from .fitscore import fitscore_replay_block
    spec = policy_spec(policy)
    fam = _KERNEL_FAMILY[spec.family]
    return fitscore_replay_block(
        carry, ev_i, ev_f, ev_size, dmask, family=fam,
        policy=policy if fam == "score" else "first_fit", n=n, d=d,
        large_bins=spec.large_bins, adaptive_alpha=spec.adaptive_alpha,
        direct_sum=spec.direct_sum, la_mode=spec.la_mode,
        la_split=LA_BINARY_SPLIT, low=spec.low, high=spec.high,
        migrate=migrate, interpret=_interpret(impl))


def dispatch_trace_count() -> int:
    """Jit-cache entry count of the block-dispatch entry point - the
    serving retrace invariant (mirrors ``sweep.runner``'s
    ``_jit_cache_entries``): after warming the fixed T geometries, mixed
    batch sizes must be pure cache hits."""
    return _fitscore_replay_dispatch_jit._cache_size()


@partial(jax.jit, static_argnames=("policy", "n", "d", "impl"))
def _fitscore_select_block_jit(loads, alive, open_seq, access_seq, closes,
                               size, pdep, now, cat=None, tags=None, *,
                               policy, n, d, impl="auto"):
    """One placement decision through the event-blocked replay megakernel
    at T=1 (``kernels.fitscore.fitscore_replay_block``): a single-lane
    carry holding the pool state replays one arrival event and the chosen
    slot is read back from the committed placement.

    ``loads`` (n, d) absolute per-replica loads; ``alive``/``open_seq``/
    ``access_seq``/``closes`` (n,); ``size`` (d,); ``pdep``/``now``
    scalars.  ``cat``+``tags`` (the request's CBD/CBDT class and the
    per-replica class tags) switch the kernel into its class-restricted
    First Fit family - the same masked select the batched replay runs.
    The pool's free-slot stage is disabled (the serving pool uses absolute,
    never-reused bin indices), so the result is (slot, found): found=False
    means "open a new replica", exactly the host algorithms' contract.
    """
    from .fitscore import (ITEMI_PLACE, SI_OPENED, SLOTF_CLOSES, SLOTI_ALIVE,
                           SLOTI_ASEQ, SLOTI_COUNTS, SLOTI_OSEQ, SLOTI_TAG,
                           ARRIVAL_KIND, KCAT, SCORE_NEG,
                           fitscore_replay_block, replay_carry_names,
                           select_pad_geometry)
    from .fitscore import ITEMI_COLS, SF_COLS, SI_COLS, SLOTF_COLS, SLOTI_COLS
    f32, i32 = jnp.float32, jnp.int32
    Np, dpad, _, _ = select_pad_geometry(n, d)
    family = "score" if cat is None else "cbd"
    sloti = jnp.zeros((1, Np, SLOTI_COLS), i32)
    sloti = sloti.at[0, :n, SLOTI_COUNTS].set(1)   # no free slots: the pool
    #                                                opens bins itself
    sloti = sloti.at[0, :n, SLOTI_ALIVE].set(alive.astype(i32))
    sloti = sloti.at[0, :n, SLOTI_OSEQ].set(open_seq.astype(i32))
    sloti = sloti.at[0, :n, SLOTI_ASEQ].set(access_seq.astype(i32))
    if tags is not None:
        sloti = sloti.at[0, :n, SLOTI_TAG].set(tags.astype(i32))
    carry = {
        "loads": jnp.zeros((1, Np, dpad), f32).at[0, :n, :d].set(
            loads.astype(f32)),
        "slotf": jnp.full((1, Np, SLOTF_COLS), 0.0, f32)
        .at[0, :, SLOTF_CLOSES].set(SCORE_NEG)
        .at[0, :n, SLOTF_CLOSES].set(closes.astype(f32)),
        "sloti": sloti,
        "itemi": jnp.full((1, 1, ITEMI_COLS), -1, i32),
        "sf": jnp.zeros((1, SF_COLS), f32),
        "si": jnp.zeros((1, SI_COLS), i32),
    }
    ev_i = {"kind": jnp.full((1, 1), ARRIVAL_KIND, i32),
            "item": jnp.zeros((1, 1), i32)}
    if cat is not None:
        ev_i["cat"] = jnp.asarray(cat, i32).reshape(1, 1)
    ev_f = {"t": jnp.asarray(now, f32).reshape(1, 1),
            "pdep": jnp.asarray(pdep, f32).reshape(1, 1)}
    ev_size = jnp.zeros((1, 1, dpad), f32).at[0, 0, :d].set(
        size.astype(f32))
    dmask = jnp.zeros((1, dpad), f32).at[0, :d].set(1.0)
    out = fitscore_replay_block(
        carry, ev_i, ev_f, ev_size, dmask, family=family,
        policy=policy if family == "score" else "first_fit", n=n, d=d,
        interpret=_interpret(impl))
    slot = out["itemi"][0, 0, ITEMI_PLACE]
    found = out["si"][0, SI_OPENED] == 0
    return slot, found
