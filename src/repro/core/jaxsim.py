"""Vectorized DVBP trace replay as a jax.lax.scan - the TPU-native engine.

The CPU oracle (core.engine) walks a heap; on an accelerator the same replay
becomes a scan over the precomputed event sequence (2n events: departures
before arrivals at equal times) with a fixed pool of bin slots.  Each step is
an O(lanes x slots x d) vector op.

``_replay_batch`` is the single replay engine for *every* policy family:

  * the score-based Any Fit family (``POLICIES``: first_fit, best_fit l1 /
    l2 / linf, mru, greedy, nrt_standard, nrt_prioritized), and
  * the category-structured families (``CATEGORY_POLICIES``): CBD / CBDT,
    Hybrid / Reduced Hybrid (+ direct-sum), RCP / PPE (+ modified),
    Lifetime Alignment (binary / geometric), and the adaptive switch.

Category policies replay in the same scan by extending the carry with
category state - a per-slot category tag (duration x arrival-window class
for the Hybrid variants, beta/rho class for CBD/CBDT, the GENERAL / BASE /
LARGE roles plus geometric prediction buckets X_i for RCP/PPE) and carried
scalars (RCP's base-bin index, PPE's guess-and-double alpha, the adaptive
switch's running departure error) - while per-item categories, thresholds
and error terms are pure functions of the (predicted) durations, computed
once before the scan from the shared categorization functions in
``core.algorithms.{duration,learned,adaptive}``.  Slot selection is then
"feasible AND category-compatible": the same fused select with an extra
category-mask input, so all families share one step body and one kernel.

Backends (``BACKENDS`` / ``resolve_backend``; "auto" = Pallas on TPU, jnp
elsewhere, override with REPRO_FITSCORE_BACKEND):

  * "jnp" - the per-step placement decision is the vmapped inline
    ``_select_slot``; the carry stays in compact (max_bins, d) layout.
  * "pallas" / "pallas_interpret" - the decision is the fused
    ``kernels.fitscore.fitscore_select_batch_padded`` kernel (feasibility +
    policy score + category mask + opening-order tie-break + free-slot
    selection in one pass over blocks of whole lanes, zero host
    round-trips per step).  The carry lives in the kernel's lane-dense
    layout (``kernels.fitscore.select_event_geometry``): loads (L, dsub,
    Np) with the dims on sublanes (d rounded up to 8) and the slots on
    lanes, laid out once before the scan (outputs are per-lane scalars).

    With ``block_events=T > 1`` the kernel backends go one rung further:
    the scan runs over *event blocks*, each block replayed entirely
    on-chip by ``kernels.fitscore.fitscore_replay_block`` (departure
    application, category update, masked select and commit for T events
    per invocation) with the packed carry resident in VMEM - the carry
    round-trips through HBM once per block instead of once per event.
    Execution knob only: decisions are identical
    (tests/test_replay_block.py).

Kernel and jnp paths are bit-identical on fp32-exact instances - the
scoring constants and policy list are imported from ``kernels.fitscore`` so
the paths cannot drift (tests/test_fitscore_select.py,
tests/test_sweep_categories.py).

Batch padding conventions (produced by ``repro.sweep.batching``):

  * events with ``kind == PAD_KIND`` are no-ops (the carry passes through
    unchanged), which is how shorter instances ride in a ``(B, 2 n_max)``
    event tensor;
  * an optional per-instance ``dmask`` marks which of the (padded) size
    dimensions are real, so best-fit scores ignore zero-padded dimensions
    (zero-size dims are always feasible but would otherwise poison the
    l_inf residual score).
"""
from __future__ import annotations

import dataclasses
import os
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fitscore import (ARRIVAL_KIND, DEPARTURE_KIND, F32_EPS,
                                FIT_CAP, IBIG, KCAT, LOC_B, LOC_C, LOC_G,
                                LOC_L,
                                MIGRATE_KIND, PAD_KIND,
                                SCORE_BIG, SCORE_NEG, SELECT_POLICIES,
                                TAG_BASE, TAG_GENERAL, TAG_LARGE, TAG_NONE,
                                TAG_VIRGIN, fitscore_replay_block,
                                fitscore_select_batch_padded,
                                replay_carry_names, select_event_geometry,
                                select_pad_geometry)
from ..kernels import fitscore as _fk
from .. import obs
from .algorithms.adaptive import pow2_ceiling_jnp, prediction_error_jnp
from .algorithms.departure import departure_window_jnp
from .algorithms.duration import (dur_exponent_jnp, duration_class_jnp,
                                  hybrid_threshold_jnp)
from .algorithms.learned import geo_class_jnp, la_class_jnp
from .types import Instance

# Scoring semantics are shared with the Pallas kernel (kernels/fitscore.py
# is the single definition site so the two paths cannot drift).
POLICIES = SELECT_POLICIES
NEG = SCORE_NEG
BIG = SCORE_BIG

# Category-structured policies replayed by the same scan (tentpole of the
# paper's headline comparisons).  Parametric variants parse too:
# "cbd_beta4", "cbdt_rho3600", "adaptive_2_16".
CATEGORY_POLICIES = ("cbd", "cbdt", "hybrid", "reduced_hybrid",
                     "hybrid_direct_sum", "reduced_hybrid_direct_sum",
                     "rcp", "ppe", "rcp_modified", "ppe_modified",
                     "la_binary", "la_geometric", "adaptive")
SCAN_POLICIES = POLICIES + CATEGORY_POLICIES

# Default CBDT window: 0.25 days, the paper's best fixed rho (Fig. 4/8).
CBDT_DEFAULT_RHO = 0.25 * 86400.0

# KCAT, the TAG_* / LOC_* carry encodings and the ARRIVAL/DEPARTURE/PAD
# event kinds are imported from kernels.fitscore (the shared definition
# site with the event-blocked replay megakernel) and re-exported here.

# Slot-pool escalation schedule shared by simulate() and repro.sweep.runner.
# The ceiling is env-overridable so capacity-constrained deployments can pin
# it below (or above) the default without code changes.
MAX_BINS_CAP = int(os.environ.get("REPRO_MAX_BINS_CAP", "65536"))


class CapacityError(RuntimeError):
    """The overflow-escalation ladder hit its ceiling and the replay still
    overflows: the instance genuinely needs more than ``max_bins_cap``
    concurrently open bins (or the cap is misconfigured).  Carries the
    offending policy / instance / final pool size so sweep drivers can
    report *which* lane blew up instead of a bare flag."""

    def __init__(self, message: str, *, policy: str = "", max_bins: int = 0,
                 instance: str = ""):
        super().__init__(message)
        self.policy = policy
        self.max_bins = max_bins
        self.instance = instance

# Scoring/selection backends.  "auto" resolves to the Pallas kernel on TPU
# and the inline jnp path elsewhere; "pallas_interpret" runs the kernel body
# in interpret mode (the CPU correctness harness).
BACKENDS = ("auto", "jnp", "pallas", "pallas_interpret")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend name (or REPRO_FITSCORE_BACKEND / "auto")."""
    backend = backend or os.environ.get("REPRO_FITSCORE_BACKEND", "auto")
    assert backend in BACKENDS, backend
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def grow_max_bins(max_bins: int, cap: int = MAX_BINS_CAP) -> int:
    """Next rung of the overflow-escalation ladder (doubling, capped)."""
    return min(max(2 * max_bins, 1), cap)


# ======================================================================
# Policy specs: one name space over both families
# ======================================================================

@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """Static description of how a policy replays in the scan."""

    family: str                 # score | cbd | cbdt | hybrid | rcp | la |
    #                             adaptive
    beta: float = 2.0           # cbd duration base
    rho: float = CBDT_DEFAULT_RHO   # cbdt departure-window width (seconds)
    reduced: bool = False       # hybrid: duration-only categories
    direct_sum: bool = False    # hybrid: per-max-dimension sub-instances
    large_bins: bool = True     # rcp/ppe: dedicated bins for items > 1/2
    adaptive_alpha: bool = False    # ppe: guess-and-double threshold
    la_mode: str = "binary"     # lifetime alignment class structure
    low: float = 2.0            # adaptive regime thresholds
    high: float = 16.0


def _policy_param(policy: str, text: str, what: str) -> float:
    """Parse one numeric parameter of a parametric policy name; unknown /
    non-numeric text is a KeyError (the "not a policy" signal)."""
    try:
        return float(text)
    except ValueError as e:   # malformed parameter, e.g. "cbd_betax"
        raise KeyError(
            f"malformed scan policy {policy!r} ({what}): {e}") from e


def policy_spec(policy: str) -> PolicySpec:
    """Parse a scan policy name (including parametric variants).

    Raises KeyError for unknown or malformed names and ValueError - at
    parse time, naming the valid range - for recognized parametric names
    whose parameter is out of range ("cbd_beta-1", "cbdt_rho0",
    "adaptive_8_2"): those values would otherwise fail deep inside the
    scan (log of a negative base, division by zero) or silently misbehave
    (an adaptive switch whose regimes never trigger)."""
    if policy in SELECT_POLICIES:
        return PolicySpec("score")
    if policy == "cbd" or policy.startswith("cbd_beta"):
        beta = 2.0 if policy == "cbd" else \
            _policy_param(policy, policy[len("cbd_beta"):], "beta")
        if not beta > 1.0:
            raise ValueError(
                f"{policy!r}: cbd beta must be > 1 (duration classes are "
                f"[beta^(i-1), beta^i)); got {beta:g}")
        return PolicySpec("cbd", beta=beta)
    if policy == "cbdt" or policy.startswith("cbdt_rho"):
        rho = CBDT_DEFAULT_RHO if policy == "cbdt" else \
            _policy_param(policy, policy[len("cbdt_rho"):], "rho")
        if not rho > 0.0:
            raise ValueError(
                f"{policy!r}: cbdt rho must be > 0 seconds (the departure-"
                f"window width); got {rho:g}")
        return PolicySpec("cbdt", rho=rho)
    if policy in ("hybrid", "reduced_hybrid", "hybrid_direct_sum",
                  "reduced_hybrid_direct_sum"):
        return PolicySpec("hybrid", reduced="reduced" in policy,
                          direct_sum="direct_sum" in policy)
    if policy in ("rcp", "ppe", "rcp_modified", "ppe_modified"):
        return PolicySpec("rcp", large_bins="modified" not in policy,
                          adaptive_alpha=policy.startswith("ppe"))
    if policy in ("la_binary", "la_geometric"):
        return PolicySpec("la", la_mode=policy[3:])
    if policy == "adaptive" or policy.startswith("adaptive_"):
        if policy == "adaptive":
            return PolicySpec("adaptive")
        parts = policy[len("adaptive_"):].split("_")
        if len(parts) != 2:
            raise KeyError(f"malformed scan policy {policy!r}: expected "
                           "adaptive_LOW_HIGH")
        low = _policy_param(policy, parts[0], "low")
        high = _policy_param(policy, parts[1], "high")
        if not 1.0 <= low <= high:
            raise ValueError(
                f"{policy!r}: adaptive thresholds need 1 <= low <= high "
                f"(departure error is >= 1 by construction); got "
                f"low={low:g} high={high:g}")
        return PolicySpec("adaptive", low=low, high=high)
    raise KeyError(f"unknown scan policy {policy!r}; known: {SCAN_POLICIES}")


def known_policy(policy: str) -> bool:
    """True when ``policy`` replays through ``_replay_batch``.  A
    recognized parametric name with an out-of-range parameter raises the
    parse-time ValueError instead of answering False - callers should see
    "cbd_beta-1" fail loudly, not fall back to a host path."""
    try:
        policy_spec(policy)
        return True
    except KeyError:
        return False


def host_algorithm(policy: str):
    """The oracle-engine algorithm instance equivalent to a scan policy
    (the parity reference used by tests and benchmarks)."""
    from .algorithms import get_algorithm
    spec = policy_spec(policy)
    if spec.family == "score":
        if policy.startswith("best_fit_"):
            return get_algorithm("best_fit", norm=policy.split("_")[-1])
        return get_algorithm(policy)
    if spec.family == "cbd":
        return get_algorithm("cbd", beta=spec.beta)
    if spec.family == "cbdt":
        return get_algorithm("cbdt", rho=spec.rho)
    if spec.family == "la":
        return get_algorithm("lifetime_alignment", mode=spec.la_mode)
    if spec.family == "adaptive":
        return get_algorithm("adaptive", low=spec.low, high=spec.high)
    return get_algorithm(policy)


@dataclasses.dataclass
class JaxSimResult:
    usage_time: float
    n_bins_opened: int
    placements: np.ndarray
    overflowed: bool
    max_bins: int = 0   # slot-pool size that produced this result


# ======================================================================
# The inline jnp placement decision (the kernel's reference twin)
# ======================================================================

def _score(policy, loads, alive, open_seq, access_seq, closes, size,
           pdep, now, dmask=None, cmask=None):
    """Lower is better; +BIG means infeasible.

    ``dmask`` (d,) marks real dimensions when sizes are zero-padded to a
    common d; zero-size padded dims never affect feasibility but must be
    excluded from the best-fit residual norms.  ``cmask`` (n_slots,)
    restricts feasibility to category-compatible slots (None = all)."""
    feasible = jnp.all(size[None, :] <= FIT_CAP - loads, axis=1) & alive
    if cmask is not None:
        feasible = feasible & cmask
    if policy == "first_fit":
        s = open_seq.astype(jnp.float32)
    elif policy == "mru":
        s = -access_seq.astype(jnp.float32)
    elif policy.startswith("best_fit"):
        after = 1.0 - loads - size[None, :]
        if policy.endswith("l1"):
            after = after if dmask is None else after * dmask
            s = after.sum(1)
        elif policy.endswith("l2"):
            after = after if dmask is None else after * dmask
            s = jnp.sqrt(jnp.sum(after * after, 1))
        else:
            if dmask is not None:
                after = jnp.where(dmask > 0, after, NEG)
            s = after.max(1)
    elif policy == "greedy":
        s = -jnp.maximum(closes, now)
    elif policy == "nrt_standard":
        s = jnp.abs(jnp.maximum(closes, now) - pdep)
    else:   # nrt_prioritized: case (a) bins strictly before case (b);
        # explicit two-stage select (a fp32 additive offset would absorb
        # the case-b ordering)
        eff = jnp.maximum(closes, now)
        gap = eff - pdep
        sa = jnp.where(feasible & (gap >= 0), gap, BIG)
        sb = jnp.where(feasible & (gap < 0), -gap, BIG)
        return jnp.where(jnp.any(sa < BIG), sa, sb)
    return jnp.where(feasible, s, BIG)


def _select_slot(policy, loads, counts, alive, open_seq, access_seq, closes,
                 size, pdep, now, dmask, cmask=None):
    """The fused placement decision, inline-jnp flavor: min score with ties
    broken by opening order (the oracle iterates open bins in opening order
    and takes the first), falling back to the smallest closed/virgin slot.
    Returns (slot, found, no_free) - the contract the Pallas kernel
    (``kernels.fitscore.fitscore_select_batch``) reproduces bit-for-bit."""
    n_slots = loads.shape[0]
    s = _score(policy, loads, alive, open_seq, access_seq, closes, size,
               pdep, now, dmask, cmask)
    smin = jnp.min(s)
    tie = s <= smin
    best = jnp.argmin(jnp.where(tie, open_seq, jnp.int32(IBIG)))
    found = smin < BIG
    free = jnp.argmin(jnp.where(counts == 0, jnp.arange(n_slots),
                                n_slots + 1))
    no_free = counts[free] != 0
    b = jnp.where(found, best, free).astype(jnp.int32)
    return b, found, no_free


# ======================================================================
# Category machinery: per-item constants + carried state per family
# ======================================================================

def _dense_key_ids(i, cls, win):
    """One lane's dense hybrid key ids: key_id[j] = first item index whose
    (i, cls, win) triple equals item j's - a valid index into an
    (n_max,)-sized aggregate table.  O(n log n) sort + segment-min (the
    pairwise-equality broadcast would be O(n^2) memory, which OOMs on
    real-trace lane sizes)."""
    n = i.shape[0]
    order = jnp.lexsort((win, cls, i))
    si, sc, sw = i[order], cls[order], win[order]
    new = jnp.concatenate([jnp.ones(1, bool), (si[1:] != si[:-1]) |
                           (sc[1:] != sc[:-1]) | (sw[1:] != sw[:-1])])
    grp = jnp.cumsum(new) - 1                   # contiguous group per key
    first = jax.ops.segment_min(order, grp, num_segments=n)
    return jnp.zeros(n, jnp.int32).at[order].set(
        first[grp].astype(jnp.int32))


def _category_state0(spec, L, item_rows, d, Np):
    """Initial carried category state for one policy family - shape-only
    (the placement-dependent state starts empty), so a streamed replay can
    build the same carry without the instance arrays.  ``item_rows`` is the
    item-table length: ``n_max`` for in-memory replays, the recycled pool
    size for streamed ones (``repro.stream``)."""
    f32, i32 = jnp.float32, jnp.int32
    tag0 = jnp.full((L, Np), TAG_VIRGIN, i32)
    if spec.family in ("score", "la"):
        return {}
    if spec.family in ("cbd", "cbdt"):
        return {"tag": tag0}
    if spec.family == "hybrid":
        return {"tag": tag0, "agg": jnp.zeros((L, item_rows, d), f32),
                "ingen": jnp.zeros((L, item_rows), bool)}
    if spec.family == "rcp":
        return {"tag": tag0,
                "agg_gen": jnp.zeros((L, KCAT, d), f32),
                "agg_cat": jnp.zeros((L, KCAT, d), f32),
                "agg_bcat": jnp.zeros((L, KCAT, d), f32),
                "agg_base": jnp.zeros((L, d), f32),
                "on": jnp.zeros((L, KCAT), bool),
                "base": jnp.full((L,), -1, i32),
                "alpha": jnp.ones((L,), f32),
                "loc": jnp.zeros((L, item_rows), i32)}
    assert spec.family == "adaptive", spec.family
    return {"err": jnp.ones((L,), f32)}


def _core_state0(loads_shape, Np, item_rows):
    """The fresh core scan carry (loads, counts, alive, open/access seq,
    closes, open_time, placements, usage, seq, opened, overflow) - exactly
    what ``_replay_batch`` starts from when ``carry0`` is None.  Loads are
    (L, Np, d) on the jnp backend and (L, dsub, Np) on the kernel ones
    (``select_event_geometry``)."""
    i32 = jnp.int32
    L = loads_shape[0]
    return (jnp.zeros(loads_shape), jnp.zeros((L, Np), i32),
            jnp.zeros((L, Np), bool),
            jnp.zeros((L, Np), i32),
            jnp.full((L, Np), -1, i32),
            jnp.full((L, Np), NEG), jnp.zeros((L, Np)),
            jnp.full((L, item_rows), -1, i32), jnp.zeros(L),
            jnp.zeros(L, i32), jnp.zeros(L, i32),
            jnp.zeros(L, bool))


def _category_setup(spec, sizes, pdeps, dmask, arrivals, rdeps, n_items,
                    times, kinds, items, Np):
    """Per-item category constants, initial carried category state, and
    extra per-event scan inputs for one policy family.

    All pure jnp on the lane-batched arrays: categories, thresholds and
    error terms are functions of the (predicted) durations only, so they
    are computed once here and the scan carries just the placement-dependent
    state (slot tags, aggregates, ON flags, alpha / err scalars)."""
    L, n_max, d = sizes.shape
    f32, i32 = jnp.float32, jnp.int32
    cat0 = _category_state0(spec, L, n_max, d, Np)
    if spec.family == "score":
        return {}, cat0, ()
    assert arrivals is not None and rdeps is not None and n_items is not None, \
        f"{spec.family} lanes need arrivals/rdeps/n_items"
    pdur = pdeps - arrivals

    if spec.family == "cbd":
        return ({"cat": duration_class_jnp(pdur, spec.beta)}, cat0, ())
    if spec.family == "cbdt":
        return ({"cat": departure_window_jnp(pdeps, spec.rho)}, cat0, ())

    if spec.family == "hybrid":
        rdur = rdeps - arrivals
        real = jnp.arange(n_max)[None, :] < n_items[:, None]
        min_dur = jnp.min(jnp.where(real, rdur, jnp.inf), axis=1)
        z = dur_exponent_jnp(min_dur)                    # (L,)
        jexp = dur_exponent_jnp(pdur)                    # (L, n_max)
        i = jnp.maximum(jexp - z[:, None] + 1, 1)        # scaled index >= 1
        thr = hybrid_threshold_jnp(i).astype(f32)
        cls = jnp.argmax(sizes, axis=2).astype(i32) if spec.direct_sum \
            else jnp.zeros((L, n_max), i32)
        win = jnp.zeros((L, n_max), i32) if spec.reduced else \
            jnp.floor(arrivals / jnp.ldexp(jnp.float32(1.0),
                                           jexp)).astype(i32)
        # dense per-lane key ids: a key (cls, i, window) is identified by
        # the first item index carrying it, so aggregates index a fixed
        # (n_max,)-sized table without host round-trips
        key = jax.vmap(_dense_key_ids)(i, cls, win)
        return {"key": key, "thr": thr, "cls": cls}, cat0, ()

    if spec.family == "rcp":
        rdur = rdeps - arrivals
        cat = jnp.clip(geo_class_jnp(jnp.maximum(pdur, 0.0)), 0, KCAT - 1)
        large = jnp.max(sizes, axis=2) > 0.5
        p2err = pow2_ceiling_jnp(
            prediction_error_jnp(rdur, pdur)).astype(f32)
        # x in the 1/sqrt(x) threshold: running count of distinct categories
        # over the arrival events - precomputable because categories are
        # pure functions of the predicted durations
        E = times.shape[1]
        is_arr = kinds == ARRIVAL_KIND
        ev_cat = jnp.take_along_axis(cat, items.astype(i32), axis=1)
        eidx = jnp.arange(E, dtype=i32)
        hot = (ev_cat[:, :, None] == jnp.arange(KCAT, dtype=i32)) & \
            is_arr[:, :, None]
        first = jnp.min(jnp.where(hot, eidx[None, :, None], E), axis=1)
        newflag = is_arr & (eidx[None, :] ==
                            jnp.take_along_axis(first, ev_cat, axis=1))
        xcount = jnp.cumsum(newflag.astype(i32), axis=1)
        return ({"cat": cat, "large": large, "p2err": p2err}, cat0,
                (xcount,))

    if spec.family == "la":
        return ({"cat": la_class_jnp(jnp.maximum(pdur, 0.0), spec.la_mode)},
                cat0, ())

    assert spec.family == "adaptive", spec.family
    rdur = rdeps - arrivals
    return ({"errmax": prediction_error_jnp(rdur, pdur).astype(f32)}, cat0,
            ())


def replay_event_extras(policy, sizes, pdeps, dmask, arrivals, rdeps,
                        n_items, times, kinds, items):
    """The per-event extra scan inputs for one policy, computed on the
    *full* event axis - what a segmented (checkpointed) replay must
    precompute once and slice per segment via ``_replay_batch``'s
    ``ev_extra``.  RCP's running distinct-category count is a cumsum over
    the whole event stream; recomputing it inside a segment would restart
    the count and change decisions.  PAD events are never arrivals, so
    tail padding leaves the cumsum undisturbed.  Returns a (possibly
    empty) tuple of (L, E) arrays."""
    spec = policy_spec(policy)
    if spec.family == "score":
        return ()
    _, _, xs_extra = _category_setup(
        spec, jnp.asarray(sizes), jnp.asarray(pdeps), dmask,
        jnp.asarray(arrivals), jnp.asarray(rdeps), jnp.asarray(n_items),
        jnp.asarray(times), jnp.asarray(kinds), jnp.asarray(items), 1)
    return xs_extra


# ======================================================================
# The event-blocked replay path (kernel backends, block_events > 1)
# ======================================================================

# policy_spec family -> megakernel family (cbd and cbdt share the
# class-restricted First Fit body; only the per-item class constant differs)
_KERNEL_FAMILY = {"score": "score", "cbd": "cbd", "cbdt": "cbd",
                  "hybrid": "hybrid", "rcp": "rcp", "la": "la",
                  "adaptive": "adaptive"}


def _replay_batch_blocked(sizes, times, kinds, items, pdeps, dmask,
                          arrivals, rdeps, n_items, *, policy: str,
                          max_bins: int, backend: str, block_events: int,
                          carry0=None, return_carry: bool = False,
                          ev_extra=None, migrate: bool = False):
    """Event-blocked replay: a short ``lax.scan`` over blocks of ``T``
    events, each block processed entirely on-chip by
    ``kernels.fitscore.fitscore_replay_block`` with the packed carry
    resident in VMEM - the carry round-trips through HBM once per block
    instead of once per event.  Decision-for-decision identical to the
    per-event paths (tests/test_replay_block.py)."""
    from .algorithms.learned import LA_BINARY_SPLIT
    spec = policy_spec(policy)
    fam = _KERNEL_FAMILY[spec.family]
    L, n_max, d = sizes.shape
    f32, i32 = jnp.float32, jnp.int32
    T = int(block_events)
    Np, dpad, _, _ = select_pad_geometry(max_bins, d)

    # pad once into the megakernel's (Np, dpad) layout
    sizes_p = jnp.asarray(sizes, f32) if dpad == d else \
        jnp.zeros((L, n_max, dpad), f32).at[:, :, :d].set(sizes)
    dm = jnp.ones((L, d), f32) if dmask is None else jnp.asarray(dmask, f32)
    dmask_p = dm if dpad == d else \
        jnp.zeros((L, dpad), f32).at[:, :d].set(dm)

    consts, _cat0, xs_extra = _category_setup(
        spec, sizes, pdeps, dmask, arrivals, rdeps, n_items, times, kinds,
        items, Np)
    if ev_extra is not None:
        # precomputed full-event-axis extras (segmented replay: RCP's
        # running distinct-category cumsum must span segments)
        xs_extra = tuple(jnp.asarray(x) for x in ev_extra)

    # per-event operand streams: pure functions of the (predicted)
    # durations, gathered by event item index and padded to a T multiple
    # with PAD_KIND no-ops (the tail block)
    items_i = jnp.asarray(items, i32)
    E = times.shape[1]
    pad = replay_scan_steps(E, backend=backend, block_events=T) - E

    def padded(a, fill):
        if pad == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((L, pad) + a.shape[2:], fill, a.dtype)], axis=1)

    def g_ev(a):
        return jnp.take_along_axis(jnp.asarray(a), items_i, axis=1)

    ev_i = {"kind": padded(jnp.asarray(kinds, i32), PAD_KIND),
            "item": padded(items_i, 0)}
    ev_f = {"t": padded(jnp.asarray(times, f32), 0.0),
            "pdep": padded(g_ev(pdeps).astype(f32), 0.0)}
    ev_size = padded(jnp.take_along_axis(sizes_p, items_i[:, :, None],
                                         axis=1), 0.0)
    if fam == "cbd":
        ev_i["cat"] = padded(g_ev(consts["cat"]).astype(i32), 0)
    elif fam == "hybrid":
        ev_i["key"] = padded(g_ev(consts["key"]).astype(i32), 0)
        ev_i["cls"] = padded(g_ev(consts["cls"]).astype(i32), 0)
        ev_f["thr"] = padded(g_ev(consts["thr"]).astype(f32), 0.0)
    elif fam == "rcp":
        ev_i["cat"] = padded(g_ev(consts["cat"]).astype(i32), 0)
        ev_i["large"] = padded(g_ev(consts["large"]).astype(i32), 0)
        ev_i["x"] = padded(xs_extra[0].astype(i32), 0)
        ev_f["p2err"] = padded(g_ev(consts["p2err"]).astype(f32), 0.0)
    elif fam == "la":
        ev_i["cat"] = padded(g_ev(consts["cat"]).astype(i32), 0)
    elif fam == "adaptive":
        ev_f["errmax"] = padded(g_ev(consts["errmax"]).astype(f32), 0.0)

    xs_streams = (ev_i, ev_f, ev_size)

    if carry0 is not None:
        # resume a segmented replay: the packed carry IS the replay state
        carry = jax.tree.map(jnp.asarray, carry0)
    else:
        carry = packed_init_carry(fam, L, n_max, max_bins, d)

    carry = _fk.fitscore_replay_chunk(
        carry, *xs_streams, dmask_p, block_events=T, family=fam,
        policy=policy if fam == "score" else "first_fit",
        n=max_bins, d=d, large_bins=spec.large_bins,
        adaptive_alpha=spec.adaptive_alpha,
        direct_sum=spec.direct_sum, la_mode=spec.la_mode,
        la_split=LA_BINARY_SPLIT, low=spec.low, high=spec.high,
        migrate=migrate, interpret=(backend == "pallas_interpret"))
    out = (carry["sf"][:, _fk.SF_USAGE],
           carry["si"][:, _fk.SI_OPENED],
           carry["itemi"][:, :, _fk.ITEMI_PLACE],
           carry["si"][:, _fk.SI_OVERFLOW] > 0)
    # usage/opened/placements live in carry columns (cumulative), so the
    # final segment of a checkpointed replay returns full-run totals
    return out + (carry,) if return_carry else out


def packed_init_carry(fam: str, L: int, item_rows: int, max_bins: int,
                      d: int):
    """A fresh packed (VMEM-layout) replay carry for the event-blocked
    megakernel path: slot closes at ``SCORE_NEG`` (virgin), tags
    ``TAG_VIRGIN``, placements -1, PPE alpha / adaptive err at 1.0, RCP
    base slot -1.  ``item_rows`` is the ``itemi`` (and hybrid ``hagg``)
    row count - ``n_max`` in-memory, the recycled pool size when streamed."""
    f32, i32 = jnp.float32, jnp.int32
    Np, dpad, _, _ = select_pad_geometry(max_bins, d)
    carry = {
        "loads": jnp.zeros((L, Np, dpad), f32),
        "slotf": jnp.zeros((L, Np, _fk.SLOTF_COLS), f32)
        .at[:, :, _fk.SLOTF_CLOSES].set(NEG),
        "sloti": jnp.zeros((L, Np, _fk.SLOTI_COLS), i32)
        .at[:, :, _fk.SLOTI_TAG].set(TAG_VIRGIN),
        "itemi": jnp.zeros((L, item_rows, _fk.ITEMI_COLS), i32)
        .at[:, :, _fk.ITEMI_PLACE].set(-1),
        "sf": jnp.zeros((L, _fk.SF_COLS), f32)
        .at[:, _fk.SF_ALPHA].set(1.0).at[:, _fk.SF_ERR].set(1.0),
        "si": jnp.zeros((L, _fk.SI_COLS), i32)
        .at[:, _fk.SI_BASE].set(-1),
    }
    if fam == "hybrid":
        carry["hagg"] = jnp.zeros((L, item_rows, dpad), f32)
    elif fam == "rcp":
        carry["ragg"] = jnp.zeros((L, _fk.RAGG_ROWS, dpad), f32)
        carry["ron"] = jnp.zeros((L, KCAT, _fk.RON_COLS), i32)
    return carry


def replay_loads_shape(L: int, max_bins: int, d: int, *, backend: str,
                       block_events: int = 0):
    """Shape of the slot loads ``_replay_batch`` carries: (L, max_bins, d)
    on the jnp backend, (L, Np, dpad) on the event-blocked megakernel
    (``select_pad_geometry``) and (L, dsub, Np) on the per-event kernel
    (``select_event_geometry``)."""
    if backend == "jnp":
        return (L, max_bins, d)
    if block_events and block_events > 1:
        Np, dpad, _, _ = select_pad_geometry(max_bins, d)
        return (L, Np, dpad)
    Np, dsub = select_event_geometry(max_bins, d)
    return (L, dsub, Np)


def replay_scan_steps(E: int, *, backend: str, block_events: int = 0,
                      trace_level: int = 0) -> int:
    """Length of the event axis ``_replay_batch`` scans for ``E`` events,
    padding included: ``E`` on the per-event paths, ``E`` rounded up to
    whole blocks on the event-blocked megakernel (kernel backends,
    ``block_events`` > 1, untraced)."""
    if backend != "jnp" and block_events and block_events > 1 and \
            not trace_level:
        return -(-E // block_events) * block_events
    return E


# Events per megakernel block of a chunked single-lane replay, chosen from
# a sweep of block sizes on a TPU v5e (PERF.md, section 6).
STREAM_BLOCK_EVENTS = 512


def replay_block_events(policy: str, *, backend: str, L: int, max_bins: int,
                        d: int, item_rows: int, chunk_events: int) -> int:
    """``block_events`` for a chunked replay of this geometry: 0 (the
    per-event scan) on jnp, the bit-exact reference, and whenever the
    packed carry with its blocks passes ``VMEM_CAP_BYTES``; otherwise
    ``STREAM_BLOCK_EVENTS``, or the whole chunk when it is shorter."""
    T = min(STREAM_BLOCK_EVENTS, int(chunk_events))
    if backend == "jnp" or T <= 1:
        return 0
    carry = jax.eval_shape(partial(
        packed_init_carry, _KERNEL_FAMILY[policy_spec(policy).family], L,
        item_rows, max_bins, d))
    vmem = _fk.replay_block_vmem_bytes(
        {nm: a.shape for nm, a in carry.items()}, T)
    return T if vmem <= _fk.VMEM_CAP_BYTES else 0


def replay_init_carry(policy: str, max_bins: int, d: int, item_rows: int,
                      *, L: int = 1, backend: str = "jnp",
                      block_events: int = 0):
    """The fresh carry ``_replay_batch`` starts from, in the layout the
    (backend, block_events) config threads across chunk boundaries - what
    a streamed replay (``repro.stream``) initializes once and then passes
    back in as ``carry0`` chunk after chunk."""
    spec = policy_spec(policy)
    if backend != "jnp" and block_events and block_events > 1:
        return packed_init_carry(_KERNEL_FAMILY[spec.family], L, item_rows,
                                 max_bins, d)
    loads_shape = replay_loads_shape(L, max_bins, d, backend=backend)
    Np = max_bins if backend == "jnp" else loads_shape[2]
    return (_core_state0(loads_shape, Np, item_rows),
            _category_state0(spec, L, item_rows, d, Np))


@lru_cache(maxsize=None)
def replay_category_bytes(policy: str, max_bins: int, d: int,
                          item_rows: int, *, L: int = 1,
                          backend: str = "jnp", block_events: int = 0) -> int:
    """Bytes that ``policy``'s family adds to the fresh carry of
    ``replay_init_carry`` over the score family's, in the same layout: the
    category state (hybrid: ``tag``, ``agg``, ``ingen`` on the per-event
    paths, ``hagg`` on the event-blocked one); 0 for the score family."""
    def nbytes(p):
        carry = jax.eval_shape(partial(
            replay_init_carry, p, max_bins, d, item_rows, L=L,
            backend=backend, block_events=block_events))
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(carry))
    return nbytes(policy) - nbytes("first_fit")


def make_live_carry(policy: str, max_bins: int, d: int,
                    max_items: int = 256):
    """A fresh single-lane packed replay carry for an *open-ended* event
    stream - the serving front end's live fleet state.

    Same layout and init as ``_replay_batch_blocked``'s carry (L=1,
    ``select_pad_geometry(max_bins, d)`` slot padding, ``max_items`` item
    rows): slot closes at ``SCORE_NEG`` (virgin), tags ``TAG_VIRGIN``,
    placements -1, PPE alpha / adaptive err at 1.0, RCP base slot -1 -
    so ``kernels.ops.fitscore_replay_dispatch`` can replay event blocks
    against it exactly as the sweep scan does, carry aliased in -> out.
    The hybrid family is clairvoyant-only (its key table is built from the
    whole instance up front) and has no live-carry form."""
    spec = policy_spec(policy)
    fam = _KERNEL_FAMILY[spec.family]
    assert fam != "hybrid", \
        f"{policy!r} is clairvoyant-only (whole-instance key table); " \
        "no live serving carry"
    return packed_init_carry(fam, 1, max_items, max_bins, d)


def grow_live_carry(carry, max_bins: int, d: int):
    """Pad a live carry's slot axis to the geometry of a larger pool (the
    serving overflow-regrow rung).  New rows are virgin - zero loads,
    ``SCORE_NEG`` closes, ``TAG_VIRGIN`` tags, zero counts - so replaying
    any overflow-free event stream on the grown carry makes the same
    decisions (extra free rows are only reached when the old pool would
    have overflowed)."""
    Np2, _, _, _ = select_pad_geometry(max_bins, d)
    Np = carry["loads"].shape[1]
    if Np2 <= Np:
        return carry
    pad = Np2 - Np

    def wide(a, fill, col=None):
        tail = jnp.zeros((1, pad) + a.shape[2:], a.dtype)
        if col is not None:
            tail = tail.at[:, :, col].set(fill)
        return jnp.concatenate([a, tail], axis=1)

    out = dict(carry)
    out["loads"] = wide(carry["loads"], 0.0)
    out["slotf"] = wide(carry["slotf"], NEG, _fk.SLOTF_CLOSES)
    out["sloti"] = wide(carry["sloti"], TAG_VIRGIN, _fk.SLOTI_TAG)
    return out


def grow_live_items(carry, max_items: int):
    """Pad a live carry's item axis (placements -1); the serving item-row
    free list doubles through this when the fleet's in-flight population
    outgrows the initial allocation."""
    n = carry["itemi"].shape[1]
    if max_items <= n:
        return carry
    out = dict(carry)
    out["itemi"] = jnp.concatenate(
        [carry["itemi"],
         jnp.zeros((1, max_items - n, _fk.ITEMI_COLS), jnp.int32)
         .at[:, :, _fk.ITEMI_PLACE].set(-1)], axis=1)
    return out


def _replay_batch(sizes, times, kinds, items, pdeps, dmask, arrivals=None,
                  rdeps=None, n_items=None, *, policy: str, max_bins: int,
                  backend: str = "jnp", block_events: int = 0,
                  trace_level: int = 0, carry0=None,
                  return_carry: bool = False, ev_extra=None,
                  migrate: bool = False):
    """``L`` lanes' event replays in lockstep: one scan over the event
    *index* whose step processes every lane at once, so the arrival scoring
    is a single (L, slots, d) op - on TPU the fused
    ``kernels.fitscore.fitscore_select_batch_padded`` Pallas kernel, with
    zero host round-trips per step.

    Every array carries a leading lane axis: sizes (L, n_max, d); times /
    kinds / items (L, 2 n_max); pdeps (L, n_max) *predicted* departures;
    ``dmask`` (L, d) real-dimension mask or None.  Category policies
    additionally need ``arrivals`` / ``rdeps`` (real departures) (L, n_max)
    and ``n_items`` (L,) to derive per-item categories, thresholds and
    departure errors (see ``_category_setup``).

    Returns (usage (L,), opened (L,), placements (L, n_max), overflow (L,)).
    With ``trace_level >= 1`` a fifth element is appended: a dict of
    stacked per-event series (each ``(L, 2 n_max, ...)``) - the chosen /
    freed slot, post-event open-bin count, per-dim aggregate load,
    category tag of the touched slot and running usage (``trace_level >= 2``
    adds the full per-slot alive mask).  ``trace_level=0`` is literally
    the pre-trace code path (``ys=None``): bit-identical outputs.

    ``backend="jnp"`` selects with the inline vmapped ``_select_slot`` on a
    compact (max_bins, d) carry; "pallas"/"pallas_interpret" run the kernel
    natively / in interpret mode with the carry held permanently in the
    kernel's lane-dense layout (``select_event_geometry``: loads
    (L, dsub, Np), dims on sublanes and slots on lanes), laid out once
    here, not per step.

    Segmented (checkpointed) replay threads the scan carry through:
    ``carry0`` resumes from a prior segment's carry, ``return_carry``
    appends the final carry to the outputs, and ``ev_extra`` overrides the
    per-event extra streams (which must be precomputed on the *full* event
    axis - RCP's distinct-category cumsum cannot restart per segment).
    See ``resilience.checkpoint.checkpointed_replay``.

    ``migrate=True`` additionally compiles the MIGRATE event branch
    (consolidation: a full departure application with the learning updates
    skipped, then the arrival machinery on the post-departure state with
    the item's source slot excluded from the select).  ``migrate=False``
    builds the exact pre-MIGRATE graph, so non-consolidating replays pay
    nothing.  See ``repro.consolidate``.
    """
    assert not (return_carry and trace_level), \
        "checkpointed replay does not stack decision traces"
    kernel_layout = backend != "jnp"
    if kernel_layout and block_events and block_events > 1 and \
            not trace_level:
        # event-blocked megakernel: whole T-event blocks on-chip, carry
        # written back to HBM once per block (kernel backends only; the
        # per-event jnp scan below stays the bit-exact reference)
        return _replay_batch_blocked(
            sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps,
            n_items, policy=policy, max_bins=max_bins, backend=backend,
            block_events=block_events, carry0=carry0,
            return_carry=return_carry, ev_extra=ev_extra, migrate=migrate)
    spec = policy_spec(policy)
    L, n_max, d = sizes.shape
    f32, i32 = jnp.float32, jnp.int32
    lanes = jnp.arange(L)
    dm = jnp.ones((L, d), f32) if dmask is None else jnp.asarray(dmask, f32)
    loads_shape = replay_loads_shape(L, max_bins, d, backend=backend)
    if kernel_layout:
        # lay out once: loads (L, dsub, Np), item sizes (L, dsub, n_max) so
        # one event's sizes gather to the kernel's (L, dsub, 1) operand
        _, dsub, Np = loads_shape
        sizes_p = jnp.zeros((L, dsub, n_max), f32).at[:, :d].set(
            jnp.swapaxes(jnp.asarray(sizes, f32), 1, 2))
        dmask_p = jnp.zeros((L, dsub, 1), f32).at[:, :d, 0].set(dm)
        slot_ids = jnp.arange(Np, dtype=i32)

        def event_size(j):
            size = jnp.take_along_axis(sizes_p, j[:, None, None], axis=2)
            return size, size[:, :d, 0]

        def at_slot(b):           # (L, 1, Np): slot b of each lane
            return (slot_ids == b[:, None])[:, None, :]

        # one masked pass over the loads, not a scatter into a strided
        # column
        def loads_add(loads, b, v):
            return jnp.where(at_slot(b), loads + v, loads)

        def loads_clear(loads, b, cond):
            return jnp.where(at_slot(b) & cond[:, None, None], 0.0, loads)

        def loads_at(loads, b):
            return jnp.take_along_axis(loads, b[:, None, None], axis=2)
    else:
        Np = max_bins
        sizes_p = jnp.asarray(sizes, f32)
        dmask_p = dm

        def event_size(j):
            size = jnp.take_along_axis(sizes_p, j[:, None, None], axis=1)
            return size[:, 0], size[:, 0]

        def loads_add(loads, b, v):
            return loads.at[lanes, b].add(v)

        def loads_clear(loads, b, cond):
            return loads.at[lanes, b].set(
                jnp.where(cond[:, None], 0.0, loads[lanes, b]))

        def loads_at(loads, b):
            return loads[lanes, b]

    consts, cat0, xs_extra = _category_setup(
        spec, sizes, pdeps, dmask, arrivals, rdeps, n_items, times, kinds,
        items, Np)
    if ev_extra is not None:
        # precomputed full-event-axis extras (segmented replay)
        xs_extra = tuple(jnp.asarray(x) for x in ev_extra)

    def do_select(base, loads, counts, alive, open_seq, access_seq, closes,
                  size, pdep_j, t, cmask=None):
        if not kernel_layout:
            return jax.vmap(partial(_select_slot, base))(
                loads, counts, alive, open_seq, access_seq, closes, size,
                pdep_j, t, dmask_p, cmask)
        return fitscore_select_batch_padded(
            loads, counts, alive, open_seq, access_seq, closes, size,
            pdep_j, t, dmask_p, cmask, policy=base, n=max_bins,
            interpret=(backend == "pallas_interpret"))

    def pick(cond, a_val, d_val):
        return jax.tree.map(
            lambda x, y: jnp.where(
                cond.reshape(cond.shape + (1,) * (x.ndim - 1)), x, y),
            a_val, d_val)

    def step(carry, ev):
        core, cat = carry
        (loads, counts, alive, open_seq, access_seq, closes, open_time,
         placements, usage, seq, opened, overflow) = core
        t, kind = ev[0], ev[1]
        j = ev[2].astype(i32)
        g = lambda a: jnp.take_along_axis(a, j[:, None], axis=1)[:, 0]
        size, size_d = event_size(j)
        pdep_j = g(pdeps)
        is_arr = kind == ARRIVAL_KIND
        is_pad = kind == PAD_KIND

        # ---- departure branch: shared bin bookkeeping
        b_dep = g(placements)
        counts_dep = counts.at[lanes, b_dep].add(-1)
        closing = counts_dep[lanes, b_dep] == 0
        usage_dep = usage + jnp.where(closing, t - open_time[lanes, b_dep],
                                      0.0)
        alive_dep = alive.at[lanes, b_dep].set(
            jnp.where(closing, False, alive[lanes, b_dep]))
        loads_dep = loads_clear(loads_add(loads, b_dep, -size), b_dep,
                                closing)
        closes_dep = closes.at[lanes, b_dep].set(
            jnp.where(closing, NEG, closes[lanes, b_dep]))

        # ---- departure-side category deltas
        cat_dep = dict(cat)   # category state if this event is a departure

        if spec.family == "hybrid":
            keyj = g(consts["key"])
            wasg = g(cat["ingen"])
            cat_dep["agg"] = cat["agg"].at[lanes, keyj].set(
                jnp.maximum(cat["agg"][lanes, keyj] -
                            jnp.where(wasg[:, None], size_d, 0.0), 0.0))

        elif spec.family == "rcp":
            # per-location aggregate decrements, category turn-OFF below
            # 1/2, alpha guess-and-double, base-close reset
            catj = g(consts["cat"])
            locd = g(cat["loc"])
            sz_g = jnp.where((locd == LOC_G)[:, None], size_d, 0.0)
            sz_b = jnp.where((locd == LOC_B)[:, None], size_d, 0.0)
            sz_c = jnp.where((locd == LOC_C)[:, None], size_d, 0.0)
            agg_gen_d = cat["agg_gen"].at[lanes, catj].set(
                jnp.maximum(cat["agg_gen"][lanes, catj] - sz_g, 0.0))
            new_cat = jnp.maximum(cat["agg_cat"][lanes, catj] - sz_c, 0.0)
            agg_cat_d = cat["agg_cat"].at[lanes, catj].set(new_cat)
            turn_off = (locd == LOC_C) & cat["on"][lanes, catj] & \
                (jnp.max(new_cat, axis=1) < 0.5)
            base_closed = closing & (cat["base"] >= 0) & \
                (b_dep == cat["base"])
            cat_dep.update(
                agg_gen=agg_gen_d, agg_cat=agg_cat_d,
                on=cat["on"].at[lanes, catj].set(
                    cat["on"][lanes, catj] & ~turn_off),
                agg_base=jnp.where(
                    base_closed[:, None], 0.0,
                    jnp.maximum(cat["agg_base"] - sz_b, 0.0)),
                agg_bcat=jnp.where(
                    base_closed[:, None, None], 0.0,
                    cat["agg_bcat"].at[lanes, catj].set(
                        jnp.maximum(cat["agg_bcat"][lanes, catj] - sz_b,
                                    0.0))),
                base=jnp.where(base_closed, -1, cat["base"]),
                alpha=jnp.maximum(cat["alpha"], g(consts["p2err"]))
                if spec.adaptive_alpha else cat["alpha"])

        elif spec.family == "adaptive":
            cat_dep["err"] = jnp.maximum(cat["err"], g(consts["errmax"]))

        # ---- the placement decision + arrival-side category deltas.
        # ``arrive`` reads only its state arguments, so the same machinery
        # serves plain arrivals (pre-event state) and - under
        # ``migrate=True`` - MIGRATE re-places (post-departure state with
        # the source slot excluded from the select).
        def arrive(core_s, cat_s, excl=None):
            (loads_s, counts_s, alive_s, open_seq_s, access_seq_s,
             closes_s, open_time_s, placements_s, usage_s, seq_s,
             opened_s, overflow_s) = core_s
            if excl is None:
                fold = lambda cm: cm
            else:
                em = jnp.arange(Np)[None, :] != excl[:, None]
                fold = lambda cm: em if cm is None else cm & em
            sel = lambda base, cmask=None: do_select(
                base, loads_s, counts_s, alive_s, open_seq_s, access_seq_s,
                closes_s, size, pdep_j, t, fold(cmask))
            cat_a = dict(cat_s)   # category state after this placement

            if spec.family == "score":
                b, found, no_free = sel(policy)

            elif spec.family in ("cbd", "cbdt"):
                # First Fit within the item's duration/departure class
                catj = g(consts["cat"])
                b, found, no_free = sel("first_fit",
                                        cat_s["tag"] == catj[:, None])
                cat_a["tag"] = cat_s["tag"].at[lanes, b].set(
                    jnp.where(found, cat_s["tag"][lanes, b], catj))

            elif spec.family == "hybrid":
                keyj, thrj, clsj = g(consts["key"]), g(consts["thr"]), \
                    g(consts["cls"])
                after = cat_s["agg"][lanes, keyj] + size_d
                norm = jnp.take_along_axis(
                    after, clsj[:, None], axis=1)[:, 0] \
                    if spec.direct_sum else jnp.max(after, axis=1)
                is_gen = norm <= thrj + F32_EPS
                wanted = jnp.where(is_gen, clsj, d + keyj)
                b, found, no_free = sel("first_fit",
                                        cat_s["tag"] == wanted[:, None])
                cat_a["tag"] = cat_s["tag"].at[lanes, b].set(
                    jnp.where(found, cat_s["tag"][lanes, b], wanted))
                cat_a["agg"] = cat_s["agg"].at[lanes, keyj].add(
                    jnp.where(is_gen[:, None], size_d, 0.0))
                cat_a["ingen"] = cat_s["ingen"].at[lanes, j].set(is_gen)

            elif spec.family == "rcp":
                catj, largej = g(consts["cat"]), g(consts["large"])
                x = jnp.maximum(ev[3], 1).astype(f32)  # distinct cats so far
                coef = cat_s["alpha"] if spec.adaptive_alpha else 1.0
                thr = coef / jnp.sqrt(x)
                fits_gen = jnp.max(cat_s["agg_gen"][lanes, catj] + size_d,
                                   axis=1) <= thr + F32_EPS
                has_base = cat_s["base"] >= 0
                base_loads = loads_at(loads_s, jnp.maximum(cat_s["base"], 0))
                base_fits = jnp.where(
                    has_base,
                    jnp.all((size <= FIT_CAP - base_loads).reshape(L, -1),
                            axis=1),
                    True)
                if excl is not None:
                    # migrate off the base bin itself: the re-place must
                    # not target its own source (the oracle's source bin is
                    # infeasible during the select)
                    base_fits = base_fits & (cat_s["base"] != excl)
                is_on = cat_s["on"][lanes, catj]
                d_large = largej if spec.large_bins else jnp.zeros(L, bool)
                d_gen = ~d_large & fits_gen
                d_cat = ~d_large & ~fits_gen & is_on
                d_base = ~d_large & ~fits_gen & ~is_on & base_fits
                d_catf = ~d_large & ~fits_gen & ~is_on & ~base_fits  # "C!"
                wanted = jnp.where(
                    d_gen, TAG_GENERAL,
                    jnp.where(d_cat, catj,
                              jnp.where(d_base & has_base, TAG_BASE,
                                        TAG_NONE)))
                b, found, no_free = sel("first_fit",
                                        cat_s["tag"] == wanted[:, None])
                open_tag = jnp.where(
                    d_large, TAG_LARGE,
                    jnp.where(d_gen, TAG_GENERAL,
                              jnp.where(d_base, TAG_BASE, catj)))
                tag_a = cat_s["tag"].at[lanes, b].set(
                    jnp.where(found, cat_s["tag"][lanes, b], open_tag))
                new_base = d_base & ~has_base
                base_a = jnp.where(new_base, b, cat_s["base"])
                agg_base_a = jnp.where(new_base[:, None], 0.0,
                                       cat_s["agg_base"]) + \
                    jnp.where(d_base[:, None], size_d, 0.0)
                agg_bcat_a = jnp.where(new_base[:, None, None], 0.0,
                                       cat_s["agg_bcat"]) \
                    .at[lanes, catj].add(
                        jnp.where(d_base[:, None], size_d, 0.0))
                agg_gen_a = cat_s["agg_gen"].at[lanes, catj].add(
                    jnp.where(d_gen[:, None], size_d, 0.0))
                agg_cat_a = cat_s["agg_cat"].at[lanes, catj].add(
                    jnp.where((d_cat | d_catf)[:, None], size_d, 0.0))
                on_a = cat_s["on"].at[lanes, catj].set(
                    cat_s["on"][lanes, catj] | d_catf)
                loc_a = cat_s["loc"].at[lanes, j].set(
                    jnp.where(d_gen, LOC_G,
                              jnp.where(d_base, LOC_B,
                                        jnp.where(d_large, LOC_L, LOC_C))))
                # base conversion (paper §VI-A): base exceeded 1/2 ->
                # becomes a category bin of its dominant member category,
                # which turns ON
                conv = d_base & (jnp.max(agg_base_a, axis=1) > 0.5)
                dom = jnp.argmax(jnp.max(agg_bcat_a, axis=2), axis=1) \
                    .astype(i32)
                tag_a = tag_a.at[lanes, b].set(
                    jnp.where(conv, dom, tag_a[lanes, b]))
                on_a = on_a.at[lanes, dom].set(on_a[lanes, dom] | conv)
                agg_cat_a = jnp.where(conv[:, None, None],
                                      agg_cat_a + agg_bcat_a, agg_cat_a)
                loc_a = jnp.where(conv[:, None] & (loc_a == LOC_B), LOC_C,
                                  loc_a)
                cat_a.update(
                    tag=tag_a, on=on_a, loc=loc_a, agg_gen=agg_gen_a,
                    agg_cat=agg_cat_a,
                    agg_base=jnp.where(conv[:, None], 0.0, agg_base_a),
                    agg_bcat=jnp.where(conv[:, None, None], 0.0,
                                       agg_bcat_a),
                    base=jnp.where(conv, -1, base_a))

            elif spec.family == "la":
                # Best Fit (l_inf) within the item's lifetime class; bins
                # are classed by predicted remaining usage (carried
                # ``closes`` clamped to now); class-0 items fill leftover
                # capacity anywhere, others fall back to foreign-class bins
                icat = g(consts["cat"])
                remt = jnp.maximum(closes_s, t[:, None]) - t[:, None]
                bincat = la_class_jnp(remt, spec.la_mode)
                same = bincat == icat[:, None]
                short = (icat == 0)[:, None]
                ra = sel("best_fit_linf", jnp.where(short, True, same))
                rb = sel("best_fit_linf", jnp.where(short, False, ~same))
                found = ra[1] | rb[1]
                b = jnp.where(ra[1], ra[0], rb[0]).astype(i32)
                no_free = ra[2]

            else:   # adaptive: regime-switch between three Any Fit
                # policies on the carried running departure error
                err = cat_s["err"]
                k = jnp.where(err < spec.low, 0,
                              jnp.where(err < spec.high, 1, 2))
                r0, r1, r2 = sel("nrt_prioritized"), sel("greedy"), \
                    sel("first_fit")
                b = jnp.where(k == 0, r0[0],
                              jnp.where(k == 1, r1[0], r2[0])).astype(i32)
                found = jnp.where(k == 0, r0[1],
                                  jnp.where(k == 1, r1[1], r2[1]))
                no_free = r0[2]

            # ---- arrival branch: shared bin bookkeeping
            b = b.astype(i32)
            overflow_arr = overflow_s | (~found & no_free)
            loads_arr = loads_add(loads_s, b, size)
            counts_arr = counts_s.at[lanes, b].add(1)
            alive_arr = alive_s.at[lanes, b].set(True)
            open_seq_arr = open_seq_s.at[lanes, b].set(
                jnp.where(found, open_seq_s[lanes, b], seq_s))
            open_time_arr = open_time_s.at[lanes, b].set(
                jnp.where(found, open_time_s[lanes, b], t))
            access_arr = access_seq_s.at[lanes, b].set(seq_s)
            closes_arr = closes_s.at[lanes, b].set(
                jnp.maximum(jnp.where(found, closes_s[lanes, b], NEG),
                            jnp.maximum(pdep_j, t)))
            placements_arr = placements_s.at[lanes, j].set(b)
            opened_arr = opened_s + jnp.where(found, 0, 1)
            return ((loads_arr, counts_arr, alive_arr, open_seq_arr,
                     access_arr, closes_arr, open_time_arr, placements_arr,
                     usage_s, seq_s + 1, opened_arr, overflow_arr),
                    cat_a, b)

        core_dep = (loads_dep, counts_dep, alive_dep, open_seq, access_seq,
                    closes_dep, open_time, placements, usage_dep, seq,
                    opened, overflow)
        core_arr, cat_arr, b_sel = arrive(core, cat)

        new = pick(is_arr, (core_arr, cat_arr), (core_dep, cat_dep))
        if migrate:
            # MIGRATE = full departure application (learning updates
            # restored: a migration is not a departure observation) then
            # the arrival machinery on the post-departure state, source
            # slot excluded from the select
            is_mig = kind == MIGRATE_KIND
            cat_migdep = dict(cat_dep)
            if spec.family == "rcp" and spec.adaptive_alpha:
                cat_migdep["alpha"] = cat["alpha"]
            elif spec.family == "adaptive":
                cat_migdep["err"] = cat["err"]
            core_mig, cat_mig, _ = arrive(core_dep, cat_migdep, b_dep)
            new = pick(is_mig, (core_mig, cat_mig), new)
        # padded events are no-ops: the carry passes through untouched
        carry = pick(is_pad, carry, new)
        if not trace_level:
            return carry, None
        # trace emission: the post-event state, as stacked scan outputs
        # (device-side tensors - the host collector never runs in here)
        core_n, cat_n = carry
        ev_slot = jnp.where(is_pad, -1,
                            jnp.where(is_arr, b_sel, b_dep)).astype(i32)
        tag_n = cat_n["tag"][lanes, jnp.maximum(ev_slot, 0)] \
            if "tag" in cat_n else jnp.full((L,), -1, i32)
        ys = {"slot": ev_slot,
              "open_bins": core_n[2].sum(axis=1).astype(i32),
              "load": core_n[0].sum(axis=2 if kernel_layout else 1)
              [:, :d].astype(jnp.float32),
              "tag": jnp.where(ev_slot >= 0, tag_n, -1).astype(i32),
              "usage": core_n[8].astype(jnp.float32)}
        if trace_level >= 2:
            ys["alive"] = core_n[2]
        return carry, ys

    core0 = _core_state0(loads_shape, Np, n_max)
    xs = tuple(jnp.swapaxes(a, 0, 1)
               for a in (times, kinds, items) + xs_extra)
    init = (core0, cat0) if carry0 is None else \
        jax.tree.map(jnp.asarray, carry0)
    (core, _cat), ys = jax.lax.scan(step, init, xs)
    out = (core[8], core[10], core[7], core[11])
    if return_carry:
        # usage/opened/placements are cumulative carry columns, so the
        # final segment of a checkpointed replay returns full-run totals
        return out + ((core, _cat),)
    if trace_level:
        # scan stacks along the leading (event) axis; traces are (L, E, .)
        return out + ({k: jnp.swapaxes(v, 0, 1) for k, v in ys.items()},)
    return out


@partial(jax.jit, static_argnames=("policy", "max_bins", "backend",
                                   "block_events"))
def _simulate_one(sizes, times, kinds, items, pdeps, arrivals, rdeps, *,
                  policy: str, max_bins: int, backend: str,
                  block_events: int = 0):
    n1 = jnp.full((1,), sizes.shape[0], jnp.int32)
    u, o, p, ov = _replay_batch(sizes[None], times[None], kinds[None],
                                items[None], pdeps[None], None,
                                arrivals[None], rdeps[None], n1,
                                policy=policy, max_bins=max_bins,
                                backend=backend, block_events=block_events)
    return u[0], o[0], p[0], ov[0]


def event_sequence(inst: Instance):
    """(times, kinds, items) int32/float arrays, departures sorted before
    arrivals at equal times (half-open [arrival, departure) intervals).
    Shared by simulate() and the repro.sweep batching layer."""
    n = inst.n_items
    times = np.concatenate([inst.arrivals, inst.departures])
    kinds = np.concatenate([np.full(n, ARRIVAL_KIND, np.int32),
                            np.full(n, DEPARTURE_KIND, np.int32)])
    items = np.concatenate([np.arange(n), np.arange(n)]).astype(np.int32)
    order = np.lexsort((np.arange(2 * n), kinds, times))
    return times[order], kinds[order], items[order]


def simulate(inst: Instance, policy: str = "first_fit",
             predicted_durations: Optional[np.ndarray] = None,
             max_bins: int = 256, auto_grow: bool = True,
             max_bins_cap: int = MAX_BINS_CAP,
             backend: Optional[str] = None,
             block_events: int = 0) -> JaxSimResult:
    """Replay one instance (any ``SCAN_POLICIES`` policy).  If the slot pool
    overflows and ``auto_grow`` is set, retries with a doubled ``max_bins``
    (up to ``max_bins_cap``) instead of returning garbage - the same
    escalation ladder the batched sweep runner applies per lane.
    ``backend`` picks the scoring engine (see ``BACKENDS``); the default
    "auto" resolves to the Pallas kernel on TPU and the inline jnp scan step
    elsewhere.  ``block_events`` > 1 (kernel backends only) replays whole
    blocks of that many events per megakernel invocation - execution
    detail, never affects results."""
    assert known_policy(policy), \
        f"{policy!r} is not a scan policy; known: {SCAN_POLICIES}"
    backend = resolve_backend(backend)
    pdeps = inst.departures if predicted_durations is None \
        else inst.arrivals + predicted_durations
    times, kinds, items = event_sequence(inst)
    args = tuple(jnp.asarray(a) for a in
                 (inst.sizes, times, kinds, items, pdeps, inst.arrivals,
                  inst.departures))
    while True:
        usage, opened, placements, overflow = _simulate_one(
            *args, policy=policy, max_bins=max_bins, backend=backend,
            block_events=block_events)
        if not bool(overflow) or not auto_grow:
            break
        if max_bins >= max_bins_cap:
            # escalation exhausted: fail structured, not with a silently
            # garbage result (auto_grow=False keeps the flag contract)
            raise CapacityError(
                f"slot pool exhausted replaying {inst.name!r} with "
                f"{policy!r}: still overflowing at max_bins={max_bins} "
                f"(cap {max_bins_cap}; raise REPRO_MAX_BINS_CAP or pass "
                f"a larger max_bins_cap)",
                policy=policy, max_bins=max_bins, instance=inst.name)
        obs.counter_add("sweep.overflow_rungs")
        max_bins = grow_max_bins(max_bins, max_bins_cap)
    return JaxSimResult(float(usage), int(opened),
                        np.asarray(placements), bool(overflow), max_bins)
