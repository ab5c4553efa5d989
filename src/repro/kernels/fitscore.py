"""DVBP placement Pallas TPU kernels - the paper's inner loop, fused.

At cloud scale an arrival must be scored against thousands of bin slots
x d resource dims: a bandwidth-bound stream over the loads matrix, ideal for
VMEM tiling.  Three kernels live here:

``fitscore`` (legacy scoring kernel)
    Tiles of 256 bins x d(pad 128) are scored per grid step: feasibility
    (all dims fit, ``EPS`` tolerance) + an l1/l2/linf fit score, and a
    running argmin in SMEM scratch emits the chosen bin directly.  Ties are
    broken by **opening order** (``open_seq``; defaults to slot index), the
    same rule the oracle engine applies when it walks open bins in opening
    order and takes the first minimum.

``fitscore_select_batch`` (the sweep scan's placement step)
    The full fused placement decision for a *batch of lanes*, covering the
    complete 8-policy score family of ``core.jaxsim`` (``SELECT_POLICIES``):
    feasibility, policy score, oracle-consistent (score, open_seq)
    lexicographic running argmin, the two-stage case-(a)/case-(b) select of
    ``nrt_prioritized``, and first-free-slot selection - one VMEM-tiled pass
    over a ``(lanes, bin-tiles)`` grid that emits the chosen slot per lane
    plus ``found`` / ``no_free`` flags.

    The optional *category mask* operand (``cmask``, (L, N) int32; 1 =
    eligible slot) restricts feasibility to category-compatible slots -
    how ``core.jaxsim`` replays the category-structured policy families
    (CBD/CBDT, Hybrid, RCP/PPE, Lifetime Alignment): their class-restricted
    First Fit / Best Fit stages are this same kernel with a mask computed
    from the carried per-slot category tags.

    ``fitscore_select_batch_padded`` is the hot-loop entry: the same
    decision for state already held in the kernel's padded (Np, dpad)
    layout (``select_pad_geometry``).  ``core.jaxsim._replay_batch`` keeps
    its whole scan carry in that layout and calls it once per event-scan
    step, so a whole sweep batch replays with zero host round-trips AND
    zero per-step re-padding (~25x redundant data traffic at d=5 before).

``fitscore_replay_block`` (the event-blocked replay megakernel)
    The next rung: instead of launching the select once per event and
    round-tripping the whole carry through HBM between scan steps, a block
    of ``T`` consecutive events is replayed *entirely on-chip* - departure
    application, category-state update, feasibility AND category-mask
    select, commit - with the packed padded carry resident in VMEM and
    written back once per block.  Covers every ``core.jaxsim`` policy
    family (score / CBD / CBDT / Hybrid / RCP-PPE / Lifetime Alignment /
    adaptive); ``core.jaxsim._replay_batch(block_events=T)`` drives it
    from a short ``lax.scan`` over event blocks, so the combined iteration
    space is (lanes, event-blocks).  The serving scheduler reuses the same
    kernel at T=1 (``kernels.ops.fitscore_select_block``).

Constants ``SCORE_BIG`` / ``SCORE_NEG`` / ``F32_EPS`` / ``FIT_CAP`` /
``IBIG`` / ``SELECT_POLICIES`` plus the replay encodings (event kinds,
TAG_* / LOC_* carry tags, KCAT) are the single source of truth for the
scoring and replay semantics; ``core.jaxsim`` and ``kernels.ops`` import
them so the inline jnp paths and the kernels can never drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-9     # legacy fitscore tolerance (matches ref.fitscore_ref)
BIG = 3.0e38   # python float: baked into the legacy kernel as an immediate

NORMS = ("l1", "l2", "linf", "first_fit")

# --- shared scoring semantics (core.jaxsim imports these; do not fork) ----
SELECT_POLICIES = ("first_fit", "best_fit_l1", "best_fit_l2", "best_fit_linf",
                   "mru", "greedy", "nrt_standard", "nrt_prioritized")
SCORE_BIG = 1e30     # +BIG == infeasible slot
SCORE_NEG = -1e30    # closes sentinel for virgin/closed slots
F32_EPS = 1e-6       # fp32 capacity tolerance (oracle uses 1e-9/f64)
# An item fits a slot when size <= FIT_CAP - load in every dimension: the
# unit capacity plus F32_EPS as one f32 constant, so the test is a single
# subtraction.  XLA's TPU compiler folds ``1.0 - load + F32_EPS`` into this
# form and Mosaic does not; written unfolded, the jnp twin and the kernels
# split on near-full slots.
FIT_CAP = float(np.float32(1.0) + np.float32(F32_EPS))
IBIG = 2 ** 30      # int sentinel for (open_seq, row) tie-break argmins

# --- shared replay semantics (single definition site; core.jaxsim
# re-exports these so the scan, the batching layer and the event-blocked
# megakernel cannot drift) -------------------------------------------------
ARRIVAL_KIND = 1     # event kinds in the precomputed sequence
DEPARTURE_KIND = 0
PAD_KIND = -1        # no-op filler event (the carry passes through)
MIGRATE_KIND = 2     # consolidation: leave current bin, re-place via the
#                      select (replay paths gate the branch on a static
#                      ``migrate`` flag so non-consolidating replays compile
#                      the exact pre-MIGRATE computation)

# Bin-role tags carried per slot (category tags are >= 0: the raw class for
# CBD/CBDT/RCP, cls / d + key for Hybrid).
TAG_VIRGIN, TAG_GENERAL, TAG_BASE, TAG_LARGE = -1, -2, -3, -4
TAG_NONE = -99       # matches no slot: forces "open a new bin"

# RCP/PPE item locations (carried per item for departure bookkeeping).
LOC_G, LOC_B, LOC_C, LOC_L = 0, 1, 2, 3

# Dense bound for RCP/PPE's carried per-category aggregates (geometric
# prediction buckets X_i; bucket 63 would need a 2^62-second duration).
KCAT = 64


def _kernel(rem_ref, alive_ref, oseq_ref, item_ref, score_ref, best_ref,
            sseq_ref, *, norm: str, bn: int, nb: int, n: int, d: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        best_ref[0] = jnp.float32(BIG)
        best_ref[1] = jnp.float32(-1.0)
        sseq_ref[0] = jnp.int32(IBIG)

    rem = rem_ref[...].astype(jnp.float32)        # (bn, dpad)
    item = item_ref[...].astype(jnp.float32)      # (1, dpad)
    after = rem - item
    dmask = jax.lax.broadcasted_iota(jnp.int32, after.shape, 1) < d
    rows = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    oseq = oseq_ref[...]                          # (bn, 1) int32
    alive = (alive_ref[...] > 0) & (rows < n)
    feasible = jnp.all((after >= -EPS) | ~dmask, axis=1, keepdims=True) & alive

    masked = jnp.where(dmask, after, 0.0)
    if norm == "l1":
        score = jnp.sum(masked, axis=1, keepdims=True)
    elif norm == "l2":
        score = jnp.sqrt(jnp.sum(masked * masked, axis=1, keepdims=True))
    elif norm == "linf":
        score = jnp.max(jnp.where(dmask, after, -BIG), axis=1, keepdims=True)
    else:   # first_fit: prefer earliest-opened feasible bin
        score = oseq.astype(jnp.float32)
    score = jnp.where(feasible, score, BIG)
    score_ref[...] = score

    # (score, open_seq) lexicographic running argmin: the oracle walks open
    # bins in opening order and keeps the first minimum, so score ties must
    # fall to the earliest-opened bin - NOT the smallest slot index (a closed
    # slot reused later has a small index but a late open_seq).
    tile_best = jnp.min(score)
    tied_seq = jnp.where((score == tile_best) & feasible, oseq, IBIG)
    tile_seq = jnp.min(tied_seq)
    tied_row = jnp.where(tied_seq == tile_seq, rows, IBIG)
    tile_arg = jnp.min(tied_row)

    better = (tile_best < best_ref[0]) | \
        ((tile_best == best_ref[0]) & (tile_seq < sseq_ref[0]))

    @pl.when(better)
    def _upd():
        best_ref[0] = tile_best
        best_ref[1] = tile_arg.astype(jnp.float32)
        sseq_ref[0] = tile_seq


def fitscore(remaining, alive, item, open_seq=None, *, norm: str = "linf",
             bn: int = 256, interpret: bool = False):
    """remaining: (N,d); alive: (N,) bool/int; item: (d,); open_seq: (N,)
    opening-order keys for tie-breaking (defaults to the slot index).
    Returns (scores (N,), best_idx scalar int32, -1 if none feasible)."""
    assert norm in NORMS
    N, d = remaining.shape
    dpad = max(128, -(-d // 128) * 128)
    bn_ = min(bn, max(N, 8))
    nb = -(-N // bn_)
    rem_p = jnp.zeros((nb * bn_, dpad), remaining.dtype)
    rem_p = rem_p.at[:N, :d].set(remaining)
    alive_p = jnp.zeros((nb * bn_, 1), jnp.int32).at[:N, 0].set(
        alive.astype(jnp.int32))
    if open_seq is None:
        open_seq = jnp.arange(N, dtype=jnp.int32)
    oseq_p = jnp.full((nb * bn_, 1), IBIG, jnp.int32).at[:N, 0].set(
        open_seq.astype(jnp.int32))
    item_p = jnp.zeros((1, dpad), remaining.dtype).at[0, :d].set(item)

    kernel = functools.partial(_kernel, norm=norm, bn=bn_, nb=nb, n=N, d=d)
    scores, best = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn_, dpad), lambda i: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, dpad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn_, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * bn_, 1), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(rem_p, alive_p, oseq_p, item_p)
    scores = jnp.where(scores[:N, 0] >= BIG, jnp.inf, scores[:N, 0])
    best_idx = jnp.where(best[0] >= BIG, -1, best[1]).astype(jnp.int32)
    return scores, best_idx


# ======================================================================
# Fused batched placement-step kernel (all 8 jaxsim policies)
# ======================================================================

def _select_kernel(loads_ref, counts_ref, alive_ref, oseq_ref, aseq_ref,
                   closes_ref, size_ref, dmask_ref, cmask_ref, pdep_ref,
                   now_ref, out_ref, fbest, ibest, *, policy: str, bn: int,
                   nb: int, n: int):
    """One (lane, bin-tile) grid step of the fused placement decision.

    Per-slot operands arrive as (1, 1, bn) blocks of (L, 1, Np) arrays and
    size/dmask as (1, 1, dpad) blocks, so every block's last two dims are
    (8, 128)-aligned or equal the array's - the TPU tiling rule at L > 1.
    ``pdep_ref`` / ``now_ref`` are whole (L,) SMEM vectors.

    ``cmask_ref`` (1, bn) int32 is the *category mask*: 1 marks slots the
    policy's category structure allows for this arrival (same-tag bins for
    CBD/CBDT/Hybrid/RCP lanes, same-lifetime-class bins for Lifetime
    Alignment; all-ones for the plain score policies).  It is folded into
    feasibility before scoring, so a lane with no category-compatible
    feasible bin reports ``found=False`` and falls through to the free-slot
    stage - exactly the host classes' "open a new bin of my category"
    contract.

    SMEM scratch layout (running state for the current lane; grid iterates
    tiles innermost so it is reset at tile 0 and emitted at tile nb-1):
      fbest[0] best case-(a) score     fbest[1] best case-(b) score
      ibest[0] case-(a) open_seq       ibest[1] case-(a) slot
      ibest[2] case-(b) open_seq       ibest[3] case-(b) slot
      ibest[4] first free slot
    Case (b) is only maintained for ``nrt_prioritized`` (its strict
    case-(a)-before-case-(b) two-stage select); every other policy uses the
    case-(a) registers alone.
    """
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        fbest[0] = jnp.float32(SCORE_BIG)
        fbest[1] = jnp.float32(SCORE_BIG)
        ibest[0] = jnp.int32(IBIG)
        ibest[1] = jnp.int32(0)
        ibest[2] = jnp.int32(IBIG)
        ibest[3] = jnp.int32(0)
        ibest[4] = jnp.int32(IBIG)

    loads = loads_ref[...].astype(jnp.float32)    # (1, bn, dpad)
    size = size_ref[0].astype(jnp.float32)        # (1, dpad)
    dmask = dmask_ref[0].astype(jnp.float32)      # (1, dpad)
    counts = counts_ref[0]                        # (1, bn) int32
    oseq = oseq_ref[0]                            # (1, bn) int32
    rows = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    rowmask = rows < n
    alive = (alive_ref[0] > 0) & rowmask
    pdep = pdep_ref[b]
    now = now_ref[b]

    # feasibility - the exact jnp expression of core.jaxsim._score,
    # restricted to category-compatible slots
    feasible = jnp.all(size[:, None, :] <= FIT_CAP - loads,
                       axis=2) & alive & (cmask_ref[0] > 0)     # (1, bn)

    if policy == "first_fit":
        s = oseq.astype(jnp.float32)
    elif policy == "mru":
        s = -aseq_ref[0].astype(jnp.float32)
    elif policy.startswith("best_fit"):
        after = 1.0 - loads - size[:, None, :]    # (1, bn, dpad)
        if policy.endswith("l1"):
            s = jnp.sum(after * dmask[:, None, :], axis=2)
        elif policy.endswith("l2"):
            masked = after * dmask[:, None, :]
            s = jnp.sqrt(jnp.sum(masked * masked, axis=2))
        else:
            s = jnp.max(jnp.where(dmask[:, None, :] > 0, after, SCORE_NEG),
                        axis=2)
    elif policy == "greedy":
        s = -jnp.maximum(closes_ref[0], now)
    elif policy == "nrt_standard":
        s = jnp.abs(jnp.maximum(closes_ref[0], now) - pdep)
    else:   # nrt_prioritized
        gap = jnp.maximum(closes_ref[0], now) - pdep
        sa = jnp.where(feasible & (gap >= 0), gap, SCORE_BIG)
        sb = jnp.where(feasible & (gap < 0), -gap, SCORE_BIG)

    def merge(score, f_slot: int, i_slot: int):
        """(score, open_seq) lexicographic running argmin over tiles."""
        tile_best = jnp.min(score)
        tied_seq = jnp.where((score == tile_best) & feasible, oseq, IBIG)
        tile_seq = jnp.min(tied_seq)
        tile_arg = jnp.min(jnp.where(tied_seq == tile_seq, rows, IBIG))
        better = (tile_best < fbest[f_slot]) | \
            ((tile_best == fbest[f_slot]) & (tile_seq < ibest[i_slot]))

        @pl.when(better)
        def _():
            fbest[f_slot] = tile_best
            ibest[i_slot] = tile_seq
            ibest[i_slot + 1] = tile_arg

    if policy == "nrt_prioritized":
        merge(sa, 0, 0)
        merge(sb, 1, 2)
    else:
        merge(jnp.where(feasible, s, SCORE_BIG), 0, 0)

    tile_free = jnp.min(jnp.where((counts == 0) & rowmask, rows, IBIG))
    ibest[4] = jnp.minimum(ibest[4], tile_free)

    @pl.when(i == nb - 1)
    def _emit():
        found_a = fbest[0] < SCORE_BIG
        if policy == "nrt_prioritized":
            found = found_a | (fbest[1] < SCORE_BIG)
            best = jnp.where(found_a, ibest[1], ibest[3])
        else:
            found = found_a
            best = ibest[1]
        no_free = ibest[4] >= IBIG
        free = jnp.where(no_free, 0, ibest[4])   # argmin-of-empty == 0 (jnp)
        out_ref[b, 0] = jnp.where(found, best, free)
        out_ref[b, 1] = found.astype(jnp.int32)
        out_ref[b, 2] = no_free.astype(jnp.int32)


def select_pad_geometry(n: int, d: int, bn: int = 256):
    """Kernel layout for an ``n``-slot, ``d``-dim pool: (Np, dpad, bn, nb).
    Shared with ``core.jaxsim`` so the scan carry can live pre-padded."""
    dpad = max(128, -(-d // 128) * 128)
    bn_ = min(bn, max(n, 8))
    nb = -(-n // bn_)
    return nb * bn_, dpad, bn_, nb


def fitscore_select_batch_padded(loads, counts, alive, open_seq, access_seq,
                                 closes, size, pdep, now, dmask, cmask=None,
                                 *, policy: str, n: int, bn: int = 256,
                                 interpret: bool = False):
    """``fitscore_select_batch`` for state already in kernel layout.

    Arguments are pre-padded per :func:`select_pad_geometry`: loads
    (L, Np, dpad); counts/alive/open_seq/access_seq/closes and the optional
    category mask ``cmask`` (L, Np); size/dmask (L, dpad); pdep/now (L,).
    ``n`` is the real slot-pool size (rows >= n are layout padding and are
    excluded from both the feasible and the free-slot stage).

    This is the replay scan's entry: ``core.jaxsim._replay_batch`` keeps its
    whole carry in this layout, so each step reads/writes the state the
    kernel consumes directly instead of re-padding (Np x dpad) every event
    (~25x redundant traffic at d=5).
    """
    assert policy in SELECT_POLICIES, policy
    L, Np, dpad = loads.shape
    Np_, dpad_, bn_, nb = select_pad_geometry(n, 1, bn)
    assert Np == Np_ and dpad % 128 == 0, (loads.shape, n, bn)
    f32, i32 = jnp.float32, jnp.int32
    if cmask is None:
        cmask = jnp.ones((L, Np), i32)
    kernel = functools.partial(_select_kernel, policy=policy, bn=bn_, nb=nb,
                               n=n)
    slot = pl.BlockSpec((1, 1, bn_), lambda b, i: (b, 0, i))
    vec = pl.BlockSpec((1, 1, dpad), lambda b, i: (b, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def row(a, dt):   # (L, X) -> (L, 1, X): unit middle axis for tiling
        return a.astype(dt).reshape(L, 1, a.shape[-1])

    out = pl.pallas_call(
        kernel,
        grid=(L, nb),
        in_specs=[pl.BlockSpec((1, bn_, dpad), lambda b, i: (b, i, 0)),
                  slot, slot, slot, slot, slot, vec, vec, slot, smem, smem],
        out_specs=smem,
        out_shape=jax.ShapeDtypeStruct((L, 3), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32),
                        pltpu.SMEM((8,), jnp.int32)],
        interpret=interpret,
    )(loads.astype(f32), row(counts, i32), row(alive, i32),
      row(open_seq, i32), row(access_seq, i32), row(closes, f32),
      row(size, f32), row(dmask, f32), row(cmask, i32),
      pdep.astype(f32).reshape(L), now.astype(f32).reshape(L))
    return out[:, 0], out[:, 1] > 0, out[:, 2] > 0


# ======================================================================
# Event-blocked replay megakernel: whole blocks of the DVBP scan on-chip
# ======================================================================
#
# ``fitscore_replay_block`` runs a block of ``T`` consecutive events of the
# replay scan - departure application, category-state update, feasibility
# AND category-mask select, and the commit - entirely inside one kernel
# invocation, for every policy family ``core.jaxsim._replay_batch``
# replays.  The padded (Np, dpad) carry stays resident in VMEM for the
# whole block and round-trips through HBM once per block instead of once
# per event; ``core.jaxsim`` drives it from a short ``lax.scan`` over
# event blocks, so the combined iteration space is (lanes, event-blocks).
#
# Carry layout (packed per lane; built by ``core.jaxsim``):
#   loads  (L, Np, dpad) f32   per-slot load vectors (kernel layout)
#   slotf  (L, Np, 8)    f32   cols: SLOTF_CLOSES, SLOTF_OPEN_TIME
#   sloti  (L, Np, 8)    i32   cols: counts, alive, open_seq, access_seq,
#                              category tag
#   itemi  (L, nmax, 8)  i32   cols: placements, family aux (hybrid ingen /
#                              rcp location)
#   sf     (L, 8)        f32   cols: usage, PPE alpha, adaptive error
#   si     (L, 8)        i32   cols: seq, opened, overflow, rcp base slot
#   hagg   (L, nmax, dpad) f32   hybrid per-key aggregates (hybrid only)
#   ragg   (L, 3*KCAT+8, dpad) f32  rcp aggregates: [gen | cat | bcat rows,
#                              base row at RAGG_BASE] (rcp only)
#   ron    (L, KCAT, 8)  i32   rcp per-category ON flags (rcp only)
#
# Per-event inputs stream in as (L, T) SMEM scalar streams plus one
# (L, T, dpad) VMEM block of pre-gathered item sizes - all pure functions
# of the (predicted) durations, precomputed before the scan.
#
# TPU tiling: a block's last two dims must be (8, 128)-aligned or equal the
# array's.  The wrapper therefore hands the kernel every per-lane 2-D array
# (sf, si, the event streams, dmask) as (L, 1, X) with a (1, 1, X) block;
# the carry's external (L, X) layout is unchanged.

SLOTF_CLOSES, SLOTF_OPEN_TIME, SLOTF_COLS = 0, 1, 8
(SLOTI_COUNTS, SLOTI_ALIVE, SLOTI_OSEQ, SLOTI_ASEQ, SLOTI_TAG,
 SLOTI_COLS) = 0, 1, 2, 3, 4, 8
ITEMI_PLACE, ITEMI_AUX, ITEMI_COLS = 0, 1, 8
SF_USAGE, SF_ALPHA, SF_ERR, SF_COLS = 0, 1, 2, 8
SI_SEQ, SI_OPENED, SI_OVERFLOW, SI_BASE, SI_COLS = 0, 1, 2, 3, 8
RAGG_BASE = 3 * KCAT           # rcp aggregate row holding the base bin
RAGG_ROWS = 3 * KCAT + 8
RON_COLS = 8

REPLAY_FAMILIES = ("score", "cbd", "hybrid", "rcp", "la", "adaptive")
# per-family extra per-event scalar streams (beyond kind/item and t/pdep)
REPLAY_EV_I = {"score": (), "cbd": ("cat",), "hybrid": ("key", "cls"),
               "rcp": ("cat", "large", "x"), "la": ("cat",),
               "adaptive": ()}
REPLAY_EV_F = {"score": (), "cbd": (), "hybrid": ("thr",),
               "rcp": ("p2err",), "la": (), "adaptive": ("errmax",)}
_REPLAY_EXTRA_CARRY = {"hybrid": ("hagg",), "rcp": ("ragg", "ron")}
_SMEM_CARRY = ("sf", "si")
# v5e has 128 MiB of VMEM per core and a 16 MiB default scoped limit.  The
# replay block raises the limit to what its blocks need plus headroom for
# the compiler's own temporaries, and refuses geometries past the cap.
VMEM_DEFAULT_BYTES = 16 * 2 ** 20
VMEM_HEADROOM_BYTES = 12 * 2 ** 20
VMEM_CAP_BYTES = 96 * 2 ** 20


def replay_carry_names(family: str):
    """Ordered carry-array names for one policy family."""
    assert family in REPLAY_FAMILIES, family
    return (("loads", "slotf", "sloti", "itemi", "sf", "si") +
            _REPLAY_EXTRA_CARRY.get(family, ()))


def _replay_block_kernel(*refs, family: str, policy: str, n: int, d: int,
                         T: int, large_bins: bool, adaptive_alpha: bool,
                         direct_sum: bool, la_mode: str, la_split: float,
                         low: float, high: float, migrate: bool, nc: int,
                         ni: int, nf: int):
    """One lane's block of ``T`` events, carry resident in VMEM.

    ``refs`` = nc carry inputs, 2+ni event int streams, 2+nf event float
    streams, ev_size, dmask, then the nc carry outputs (aliased to the
    inputs).  The body is the exact fp32 op sequence of the jnp reference
    step (``core.jaxsim._replay_batch``) scalarized per lane: per-slot
    state updates are masked vector ops over (Np, 1) columns, per-item and
    per-category aggregate rows use dynamic sublane slices.
    """
    f32, i32 = jnp.float32, jnp.int32
    names = replay_carry_names(family)
    cin = dict(zip(names, refs[:nc]))
    k = nc
    evi = dict(zip(("kind", "item") + REPLAY_EV_I[family],
                   refs[k:k + 2 + ni]))
    k += 2 + ni
    evf = dict(zip(("t", "pdep") + REPLAY_EV_F[family], refs[k:k + 2 + nf]))
    k += 2 + nf
    size_ref, dmask_ref = refs[k], refs[k + 1]
    c = dict(zip(names, refs[k + 2:k + 2 + nc]))

    # one HBM->VMEM copy per block: every event below reads and writes the
    # (aliased) out blocks only.  SMEM holds scalars only, so the (1, 1, 8)
    # scalar carries copy element by element.
    for nm in names:
        if nm in _SMEM_CARRY:
            for col in range(c[nm].shape[2]):
                c[nm][0, 0, col] = cin[nm][0, 0, col]
        else:
            c[nm][...] = cin[nm][...]

    Np = c["loads"].shape[1]
    rowsN = jax.lax.broadcasted_iota(i32, (Np, 1), 0)
    rowmask = rowsN < n
    rowsK = jax.lax.broadcasted_iota(i32, (KCAT, 1), 0)
    colsI = jax.lax.broadcasted_iota(i32, (1, ITEMI_COLS), 1)
    dm = dmask_ref[0]                                     # (1, dpad)

    def scol_i(col):
        return c["sloti"][0, :, col:col + 1]              # (Np, 1) i32

    def scol_f(col):
        return c["slotf"][0, :, col:col + 1]              # (Np, 1) f32

    def set_scol_i(col, v):
        c["sloti"][0, :, col:col + 1] = v

    def set_scol_f(col, v):
        c["slotf"][0, :, col:col + 1] = v

    def at_slot(colv, b, zero):
        return jnp.sum(jnp.where(rowsN == b, colv, zero))

    def at_item(col, j):
        row = c["itemi"][0, pl.ds(j, 1), :]               # (1, ITEMI_COLS)
        return jnp.sum(jnp.where(colsI == col, row, 0))

    def set_item(col, j, v):
        row = c["itemi"][0, pl.ds(j, 1), :]
        c["itemi"][0, pl.ds(j, 1), :] = jnp.where(colsI == col, v, row)

    def body(e, _):
        kind = evi["kind"][0, 0, e]
        j = evi["item"][0, 0, e]
        t = evf["t"][0, 0, e]
        pdep = evf["pdep"][0, 0, e]
        size = size_ref[0, pl.ds(e, 1), :]                # (1, dpad)

        def select(pol, cmask, excl=None):
            """The fused placement decision on the current carry - the
            exact semantics of ``_select_kernel`` / ``_select_slot``.
            ``excl`` (migrate re-place only) removes one slot - the item's
            source bin - from feasibility, never from the free-slot stage.

            Deliberately a third expression of the shared scoring
            semantics (per-lane (Np, 1) columns here vs the tiled
            (1, bn) SMEM-register select kernel): the three stay pinned
            together by the shared SCORE_*/FIT_CAP/IBIG constants and the
            bitwise parity matrix in tests/test_fitscore_select.py +
            tests/test_replay_block.py - any drift fails those, so edit
            all three together when touching a policy's score."""
            loads2 = c["loads"][0]                        # (Np, dpad)
            cnt = scol_i(SLOTI_COUNTS)
            oseq = scol_i(SLOTI_OSEQ)
            closes = scol_f(SLOTF_CLOSES)
            feas = jnp.all(size <= FIT_CAP - loads2, axis=1,
                           keepdims=True) & \
                (scol_i(SLOTI_ALIVE) > 0) & rowmask
            if excl is not None:
                feas = feas & (rowsN != excl)
            if cmask is not None:
                feas = feas & cmask

            def run_min(s, fm):
                s = jnp.where(fm, s, SCORE_BIG)
                smin = jnp.min(s)
                tied = jnp.where((s == smin) & fm, oseq, IBIG)
                tseq = jnp.min(tied)
                trow = jnp.min(jnp.where(tied == tseq, rowsN, IBIG))
                return smin, trow

            if pol == "nrt_prioritized":
                gap = jnp.maximum(closes, t) - pdep
                amin, arow = run_min(jnp.where(gap >= 0, gap, SCORE_BIG),
                                     feas)
                bmin, brow = run_min(jnp.where(gap < 0, -gap, SCORE_BIG),
                                     feas)
                found = (amin < SCORE_BIG) | (bmin < SCORE_BIG)
                best = jnp.where(amin < SCORE_BIG, arow, brow)
            else:
                if pol == "first_fit":
                    s = oseq.astype(f32)
                elif pol == "mru":
                    s = -scol_i(SLOTI_ASEQ).astype(f32)
                elif pol.startswith("best_fit"):
                    after = 1.0 - loads2 - size
                    if pol.endswith("l1"):
                        s = jnp.sum(after * dm, axis=1, keepdims=True)
                    elif pol.endswith("l2"):
                        m_ = after * dm
                        s = jnp.sqrt(jnp.sum(m_ * m_, axis=1,
                                             keepdims=True))
                    else:
                        s = jnp.max(jnp.where(dm > 0, after, SCORE_NEG),
                                    axis=1, keepdims=True)
                elif pol == "greedy":
                    s = -jnp.maximum(closes, t)
                else:   # nrt_standard
                    s = jnp.abs(jnp.maximum(closes, t) - pdep)
                smin, best = run_min(s, feas)
                found = smin < SCORE_BIG
            fr = jnp.min(jnp.where((cnt == 0) & rowmask, rowsN, IBIG))
            no_free = fr >= IBIG
            b = jnp.where(found, best, jnp.where(no_free, 0, fr))
            return b.astype(i32), found, no_free

        # ------------------------------------------------ departure branch
        def dep_apply(learn: bool):
            """Remove item ``j`` from its bin: shared bin bookkeeping plus
            the per-family aggregate decrements.  ``learn=False`` is the
            migrate flavor - a migration is not a departure *observation*,
            so the departure-driven learning updates (PPE's alpha
            guess-and-double, the adaptive switch's running error) are
            skipped."""
            b = at_item(ITEMI_PLACE, j)
            rm = rowsN == b
            cnt = scol_i(SLOTI_COUNTS) - rm.astype(i32)
            closing = at_slot(cnt, b, 0) == 0
            ot_b = at_slot(scol_f(SLOTF_OPEN_TIME), b, 0.0)
            c["sf"][0, 0, SF_USAGE] = c["sf"][0, 0, SF_USAGE] + \
                jnp.where(closing, t - ot_b, 0.0)
            loads2 = c["loads"][0]
            loads2 = jnp.where(rm, loads2 - size, loads2)
            c["loads"][0, :, :] = jnp.where(rm & closing, 0.0, loads2)
            set_scol_i(SLOTI_COUNTS, cnt)
            set_scol_i(SLOTI_ALIVE,
                       jnp.where(rm & closing, 0, scol_i(SLOTI_ALIVE)))
            set_scol_f(SLOTF_CLOSES,
                       jnp.where(rm & closing, SCORE_NEG,
                                 scol_f(SLOTF_CLOSES)))

            if family == "hybrid":
                keyj = evi["key"][0, 0, e]
                wasg = at_item(ITEMI_AUX, j) > 0
                row = c["hagg"][0, pl.ds(keyj, 1), :]
                c["hagg"][0, pl.ds(keyj, 1), :] = jnp.maximum(
                    row - jnp.where(wasg, size, 0.0), 0.0)
            elif family == "rcp":
                catj = evi["cat"][0, 0, e]
                locd = at_item(ITEMI_AUX, j)
                base = c["si"][0, 0, SI_BASE]
                has_base = base >= 0
                gen_row = c["ragg"][0, pl.ds(catj, 1), :]
                c["ragg"][0, pl.ds(catj, 1), :] = jnp.maximum(
                    gen_row - jnp.where(locd == LOC_G, size, 0.0), 0.0)
                cat_row = c["ragg"][0, pl.ds(KCAT + catj, 1), :]
                new_cat = jnp.maximum(
                    cat_row - jnp.where(locd == LOC_C, size, 0.0), 0.0)
                c["ragg"][0, pl.ds(KCAT + catj, 1), :] = new_cat
                oncol = c["ron"][0, :, 0:1]
                on_cat = jnp.sum(jnp.where(rowsK == catj, oncol, 0)) > 0
                turn_off = (locd == LOC_C) & on_cat & \
                    (jnp.max(new_cat) < 0.5)
                c["ron"][0, :, 0:1] = jnp.where((rowsK == catj) & turn_off,
                                                0, oncol)
                base_closed = closing & has_base & (b == base)
                sz_b = jnp.where(locd == LOC_B, size, 0.0)
                base_row = c["ragg"][0, RAGG_BASE:RAGG_BASE + 1, :]
                c["ragg"][0, RAGG_BASE:RAGG_BASE + 1, :] = jnp.where(
                    base_closed, 0.0, jnp.maximum(base_row - sz_b, 0.0))
                bcat = c["ragg"][0, 2 * KCAT:3 * KCAT, :]
                bcat = jnp.where(rowsK == catj,
                                 jnp.maximum(bcat - sz_b, 0.0), bcat)
                c["ragg"][0, 2 * KCAT:3 * KCAT, :] = jnp.where(
                    base_closed, 0.0, bcat)
                c["si"][0, 0, SI_BASE] = jnp.where(base_closed, -1, base)
                if adaptive_alpha and learn:
                    c["sf"][0, 0, SF_ALPHA] = jnp.maximum(
                        c["sf"][0, 0, SF_ALPHA], evf["p2err"][0, 0, e])
            elif family == "adaptive" and learn:
                c["sf"][0, 0, SF_ERR] = jnp.maximum(c["sf"][0, 0, SF_ERR],
                                                 evf["errmax"][0, 0, e])

        @pl.when(kind == DEPARTURE_KIND)
        def _dep():
            dep_apply(True)

        # -------------------------------------------------- arrival branch
        def arr_apply(excl):
            """Place item ``j``: the per-family decision + the shared
            commit.  ``excl`` (migrate re-place only) keeps the select off
            the item's source slot."""
            tag = scol_i(SLOTI_TAG)
            post = None      # family commit, needs (b, rm, found)

            if family == "score":
                b, found, no_free = select(policy, None, excl)

            elif family == "cbd":
                catj = evi["cat"][0, 0, e]
                b, found, no_free = select("first_fit", tag == catj, excl)

                def post(b, rm, found):
                    set_scol_i(SLOTI_TAG,
                               jnp.where(rm & ~found, catj, tag))

            elif family == "hybrid":
                keyj = evi["key"][0, 0, e]
                clsj = evi["cls"][0, 0, e]
                thrj = evf["thr"][0, 0, e]
                aggrow = c["hagg"][0, pl.ds(keyj, 1), :]
                after = aggrow + size
                if direct_sum:
                    cols = jax.lax.broadcasted_iota(i32, after.shape, 1)
                    norm = jnp.sum(jnp.where(cols == clsj, after, 0.0))
                else:
                    norm = jnp.max(after)
                is_gen = norm <= thrj + F32_EPS
                wanted = jnp.where(is_gen, clsj, d + keyj)
                b, found, no_free = select("first_fit", tag == wanted, excl)

                def post(b, rm, found):
                    set_scol_i(SLOTI_TAG,
                               jnp.where(rm & ~found, wanted, tag))
                    c["hagg"][0, pl.ds(keyj, 1), :] = aggrow + \
                        jnp.where(is_gen, size, 0.0)
                    set_item(ITEMI_AUX, j, is_gen.astype(i32))

            elif family == "rcp":
                catj = evi["cat"][0, 0, e]
                largej = evi["large"][0, 0, e] > 0
                x = jnp.maximum(evi["x"][0, 0, e], 1).astype(f32)
                coef = c["sf"][0, 0, SF_ALPHA] if adaptive_alpha else 1.0
                thr = coef / jnp.sqrt(x)
                gen_row = c["ragg"][0, pl.ds(catj, 1), :]
                fits_gen = jnp.max(gen_row + size) <= thr + F32_EPS
                base = c["si"][0, 0, SI_BASE]
                has_base = base >= 0
                base_loads = c["loads"][0, pl.ds(jnp.maximum(base, 0), 1), :]
                base_fits = jnp.where(
                    has_base,
                    jnp.all(size <= FIT_CAP - base_loads), True)
                if excl is not None:
                    # migrate off the base bin itself: the re-place must
                    # not target its own source (matches the host oracle,
                    # where the source bin is infeasible during the select)
                    base_fits = base_fits & (base != excl)
                oncol = c["ron"][0, :, 0:1]
                is_on = jnp.sum(jnp.where(rowsK == catj, oncol, 0)) > 0
                d_large = largej if large_bins else False
                d_gen = ~d_large & fits_gen
                d_cat = ~d_large & ~fits_gen & is_on
                d_base = ~d_large & ~fits_gen & ~is_on & base_fits
                d_catf = ~d_large & ~fits_gen & ~is_on & ~base_fits
                wanted = jnp.where(
                    d_gen, TAG_GENERAL,
                    jnp.where(d_cat, catj,
                              jnp.where(d_base & has_base, TAG_BASE,
                                        TAG_NONE)))
                b, found, no_free = select("first_fit", tag == wanted, excl)

                def post(b, rm, found):
                    open_tag = jnp.where(
                        d_large, TAG_LARGE,
                        jnp.where(d_gen, TAG_GENERAL,
                                  jnp.where(d_base, TAG_BASE, catj)))
                    tag1 = jnp.where(rm & ~found, open_tag, tag)
                    new_base = d_base & ~has_base
                    base_a = jnp.where(new_base, b, base)
                    # aggregates: general / category adds, base-zeroing on
                    # a fresh base bin, then the 1/2-threshold conversion
                    c["ragg"][0, pl.ds(catj, 1), :] = gen_row + \
                        jnp.where(d_gen, size, 0.0)
                    cat_row = c["ragg"][0, pl.ds(KCAT + catj, 1), :]
                    cat_row = cat_row + jnp.where(d_cat | d_catf, size, 0.0)
                    bcat = c["ragg"][0, 2 * KCAT:3 * KCAT, :]
                    bcat = jnp.where(new_base, 0.0, bcat)
                    bcat = jnp.where(rowsK == catj,
                                     bcat + jnp.where(d_base, size, 0.0),
                                     bcat)
                    base_row = c["ragg"][0, RAGG_BASE:RAGG_BASE + 1, :]
                    base_row = jnp.where(new_base, 0.0, base_row) + \
                        jnp.where(d_base, size, 0.0)
                    onc = jnp.where(rowsK == catj,
                                    oncol | d_catf.astype(i32), oncol)
                    set_item(ITEMI_AUX, j, jnp.where(
                        d_gen, LOC_G,
                        jnp.where(d_base, LOC_B,
                                  jnp.where(d_large, LOC_L, LOC_C))))
                    # base conversion (paper §VI-A): base exceeded 1/2 ->
                    # becomes a category bin of its dominant member
                    # category, which turns ON
                    conv = d_base & (jnp.max(base_row) > 0.5)
                    bmax = jnp.max(bcat, axis=1, keepdims=True)   # (KCAT,1)
                    mmax = jnp.max(bmax)
                    dom = jnp.min(jnp.where(bmax == mmax, rowsK, IBIG))
                    tag1 = jnp.where(rm & conv, dom, tag1)
                    onc = jnp.where(rowsK == dom, onc | conv.astype(i32),
                                    onc)
                    cat_row = jnp.where(
                        conv,
                        cat_row + jnp.sum(
                            jnp.where(rowsK == catj, bcat, 0.0), axis=0,
                            keepdims=True),
                        cat_row)
                    catblk = c["ragg"][0, KCAT:2 * KCAT, :]
                    # whole-block add of bcat into cat on conversion; the
                    # catj row was already read out, so write it last
                    catblk = jnp.where(conv, catblk + bcat, catblk)
                    catblk = jnp.where(rowsK == catj, cat_row, catblk)
                    c["ragg"][0, KCAT:2 * KCAT, :] = catblk
                    aux = c["itemi"][0, :, ITEMI_AUX:ITEMI_AUX + 1]
                    c["itemi"][0, :, ITEMI_AUX:ITEMI_AUX + 1] = jnp.where(
                        conv & (aux == LOC_B), LOC_C, aux)
                    set_scol_i(SLOTI_TAG, tag1)
                    c["ron"][0, :, 0:1] = onc
                    c["ragg"][0, 2 * KCAT:3 * KCAT, :] = jnp.where(
                        conv, 0.0, bcat)
                    c["ragg"][0, RAGG_BASE:RAGG_BASE + 1, :] = jnp.where(
                        conv, 0.0, base_row)
                    c["si"][0, 0, SI_BASE] = jnp.where(conv, -1, base_a)

            elif family == "la":
                icat = evi["cat"][0, 0, e]
                remt = jnp.maximum(scol_f(SLOTF_CLOSES), t) - t   # (Np, 1)
                if la_mode == "binary":
                    bincat = (remt >= la_split).astype(i32)
                else:   # geometric: frexp exponent via the f32 bit pattern
                    bits = jax.lax.bitcast_convert_type(remt, i32)
                    bexp = ((bits >> 23) & 0xFF) - 126
                    bincat = jnp.where(remt < 1.0, 0, bexp)
                same = bincat == icat
                short = icat == 0
                ra = select("best_fit_linf", same | short, excl)
                rb = select("best_fit_linf", (~same) & ~short, excl)
                found = ra[1] | rb[1]
                b = jnp.where(ra[1], ra[0], rb[0]).astype(i32)
                no_free = ra[2]

            else:   # adaptive: regime-switch on the carried departure error
                err = c["sf"][0, 0, SF_ERR]
                kreg = jnp.where(err < low, 0, jnp.where(err < high, 1, 2))
                r0 = select("nrt_prioritized", None, excl)
                r1 = select("greedy", None, excl)
                r2 = select("first_fit", None, excl)
                b = jnp.where(kreg == 0, r0[0],
                              jnp.where(kreg == 1, r1[0], r2[0])).astype(i32)
                found = jnp.where(kreg == 0, r0[1],
                                  jnp.where(kreg == 1, r1[1], r2[1]))
                no_free = r0[2]

            # ---- shared commit
            rm = rowsN == b
            seq = c["si"][0, 0, SI_SEQ]
            loads2 = c["loads"][0]
            c["loads"][0, :, :] = jnp.where(rm, loads2 + size, loads2)
            set_scol_i(SLOTI_COUNTS, scol_i(SLOTI_COUNTS) + rm.astype(i32))
            set_scol_i(SLOTI_ALIVE,
                       jnp.where(rm, 1, scol_i(SLOTI_ALIVE)))
            set_scol_i(SLOTI_OSEQ,
                       jnp.where(rm & ~found, seq, scol_i(SLOTI_OSEQ)))
            set_scol_f(SLOTF_OPEN_TIME,
                       jnp.where(rm & ~found, t, scol_f(SLOTF_OPEN_TIME)))
            set_scol_i(SLOTI_ASEQ, jnp.where(rm, seq, scol_i(SLOTI_ASEQ)))
            closes = scol_f(SLOTF_CLOSES)
            set_scol_f(SLOTF_CLOSES, jnp.where(
                rm,
                jnp.maximum(jnp.where(found, closes, SCORE_NEG),
                            jnp.maximum(pdep, t)),
                closes))
            set_item(ITEMI_PLACE, j, b)
            c["si"][0, 0, SI_OPENED] = c["si"][0, 0, SI_OPENED] + \
                (~found).astype(i32)
            c["si"][0, 0, SI_OVERFLOW] = c["si"][0, 0, SI_OVERFLOW] | \
                ((~found) & no_free).astype(i32)
            c["si"][0, 0, SI_SEQ] = seq + 1
            if post is not None:
                post(b, rm, found)

        @pl.when(kind == ARRIVAL_KIND)
        def _arr():
            arr_apply(None)

        if migrate:
            # consolidation: a MIGRATE event is a full departure (learning
            # updates skipped) followed by the arrival machinery evaluated
            # on the post-departure carry, with the source slot excluded
            # from the select.  Compiled only when the replay carries
            # migrations - migrate=False is the exact pre-MIGRATE kernel.
            @pl.when(kind == MIGRATE_KIND)
            def _mig():
                src = at_item(ITEMI_PLACE, j)
                dep_apply(False)
                arr_apply(src)
        return 0

    jax.lax.fori_loop(0, T, body, 0)


def fitscore_replay_block(carry, ev_i, ev_f, ev_size, dmask, *, family: str,
                          policy: str, n: int, d: int,
                          large_bins: bool = True,
                          adaptive_alpha: bool = False,
                          direct_sum: bool = False, la_mode: str = "binary",
                          la_split: float = 7200.0, low: float = 2.0,
                          high: float = 16.0, migrate: bool = False,
                          interpret: bool = False):
    """Replay one block of ``T`` events for ``L`` lanes entirely on-chip.

    ``carry`` is a dict of the packed per-lane carry arrays (see the
    section comment above; ``replay_carry_names(family)`` lists them);
    ``ev_i`` / ``ev_f`` map stream names to (L, T) int32/float32 arrays
    (always ``kind``/``item`` resp. ``t``/``pdep`` plus the family's
    ``REPLAY_EV_I`` / ``REPLAY_EV_F`` extras); ``ev_size`` is the
    (L, T, dpad) pre-gathered item sizes and ``dmask`` the (L, dpad)
    real-dimension mask.  ``n`` is the real slot-pool size, ``d`` the real
    dimension count (hybrid tags encode ``d + key``).

    Returns the updated carry dict.  The big VMEM carry arrays are aliased
    input->output, so under jit the block update is in-place in HBM: the
    carry round-trips through HBM once per *block* instead of once per
    event (the per-event fused-select path re-reads and re-writes it every
    scan step).

    ``migrate=True`` additionally compiles the MIGRATE event branch
    (consolidation: departure + masked re-place in one event); the default
    False generates the exact migration-free kernel, so non-consolidating
    replays pay nothing for the third event kind.
    """
    names = replay_carry_names(family)
    assert set(names) == set(carry), (names, sorted(carry))
    ev_i_names = ("kind", "item") + REPLAY_EV_I[family]
    ev_f_names = ("t", "pdep") + REPLAY_EV_F[family]
    L, T, dpad = ev_size.shape
    carr = [carry[nm] for nm in names]
    vmem = replay_block_vmem_bytes({nm: a.shape for nm, a in carry.items()},
                                   T)
    if not interpret and vmem > VMEM_CAP_BYTES:
        raise ValueError(
            f"replay block geometry does not fit VMEM: family={family} "
            f"L={L} item_rows={carry['itemi'].shape[1]} "
            f"Np={carry['loads'].shape[1]} dpad={dpad} T={T} needs "
            f"{vmem / 2**20:.1f} MiB > {VMEM_CAP_BYTES / 2**20:.0f} MiB; "
            "use a smaller item pool (repro.stream item_rows) or max_bins")

    def row(a):   # (L, X) -> (L, 1, X): unit middle axis for tiling
        return a.reshape(a.shape[0], 1, a.shape[-1])

    def spec(shape, smem=False):
        nz = (0,) * (len(shape) - 1)
        return pl.BlockSpec((1,) + tuple(shape[1:]), lambda b: (b,) + nz,
                            memory_space=pltpu.SMEM if smem else None)

    args = [row(a) if nm in _SMEM_CARRY else a for nm, a in zip(names, carr)]
    cspecs = [spec(a.shape, nm in _SMEM_CARRY) for nm, a in zip(names, args)]
    streams = [row(ev_i[nm]) for nm in ev_i_names] + \
        [row(ev_f[nm]) for nm in ev_f_names]
    in_specs = cspecs + [spec(a.shape, True) for a in streams] + \
        [spec(ev_size.shape), spec((L, 1, dpad))]
    kernel = functools.partial(
        _replay_block_kernel, family=family, policy=policy, n=n, d=d, T=T,
        large_bins=large_bins, adaptive_alpha=adaptive_alpha,
        direct_sum=direct_sum, la_mode=la_mode, la_split=la_split, low=low,
        high=high, migrate=migrate, nc=len(names),
        ni=len(REPLAY_EV_I[family]), nf=len(REPLAY_EV_F[family]))
    outs = pl.pallas_call(
        kernel,
        grid=(L,),
        in_specs=in_specs,
        out_specs=cspecs,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args],
        input_output_aliases={idx: idx for idx, nm in enumerate(names)
                              if nm not in _SMEM_CARRY},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem + VMEM_HEADROOM_BYTES,
                                 VMEM_DEFAULT_BYTES)),
        interpret=interpret,
    )(*args, *streams, ev_size, row(dmask))
    return {nm: o.reshape(carry[nm].shape) for nm, o in zip(names, outs)}


def _vmem_tile_bytes(shape) -> int:
    """Bytes of one 32-bit VMEM buffer of ``shape`` after (8, 128) tiling."""
    *lead, r, c = shape
    lead_n = 1
    for x in lead:
        lead_n *= x
    return lead_n * (-(-r // 8) * 8) * (-(-c // 128) * 128) * 4


def replay_block_vmem_bytes(carry_shapes, T: int) -> int:
    """VMEM one ``fitscore_replay_block`` grid step holds: every VMEM carry
    block in and out, plus the event-size and dmask blocks, each double
    buffered by the pipeline.  ``carry_shapes`` maps carry names to their
    (L, ...) shapes."""
    dpad = carry_shapes["loads"][2]
    carry = sum(_vmem_tile_bytes(shp[1:]) for nm, shp in carry_shapes.items()
                if nm not in _SMEM_CARRY)
    return 2 * (2 * carry + _vmem_tile_bytes((T, dpad)) +
                _vmem_tile_bytes((1, dpad)))


def fitscore_replay_chunk(carry, ev_i, ev_f, ev_size, dmask, *,
                          block_events: int, **block_kwargs):
    """Chunk-boundary replay entry: ``lax.scan`` of
    :func:`fitscore_replay_block` over a fixed-geometry chunk of
    ``C = NB * block_events`` events - the unit of device work for both the
    event-blocked in-memory path (``core.jaxsim._replay_batch_blocked``)
    and the streamed replay (``repro.stream``), which threads the returned
    packed carry into the next chunk.

    ``ev_i`` / ``ev_f`` are dicts of (L, C) event streams, ``ev_size`` the
    (L, C, dpad) pre-gathered sizes; C must be a multiple of
    ``block_events`` (pad the tail with ``PAD_KIND`` no-ops - the carry
    passes through them, so padding never changes decisions).  Because the
    carry after any block equals the carry the per-event scan would hold at
    the same event index, a replay chunked at *any* block-aligned boundary
    is bit-identical to the unchunked one (tests/test_stream.py)."""
    T = int(block_events)
    L, C, _ = ev_size.shape
    assert T >= 1 and C % T == 0, (C, T)
    NB = C // T

    def blocks(a):
        return jnp.swapaxes(a.reshape((L, NB, T) + a.shape[2:]), 0, 1)

    xs = (jax.tree.map(blocks, ev_i), jax.tree.map(blocks, ev_f),
          blocks(ev_size))

    def step(c, ev):
        evi_b, evf_b, size_b = ev
        return fitscore_replay_block(c, evi_b, evf_b, size_b, dmask,
                                     **block_kwargs), None

    carry, _ = jax.lax.scan(step, carry, xs)
    return carry


def fitscore_select_batch(loads, counts, alive, open_seq, access_seq, closes,
                          size, pdep, now, dmask, cmask=None, *, policy: str,
                          bn: int = 256, interpret: bool = False):
    """Fused batched DVBP placement step over ``L`` independent lanes.

    loads: (L, N, d) per-slot load vectors; counts/alive/open_seq/access_seq/
    closes: (L, N) slot state; size: (L, d) arriving item; pdep/now: (L,)
    scalars; dmask: (L, d) real-dimension mask (1.0 real / 0.0 padding);
    cmask: optional (L, N) category mask (1 = category-compatible slot, see
    ``_select_kernel``; None = unrestricted).

    Returns ``(slot, found, no_free)``, each ``(L,)`` - the slot the policy
    places into (the best feasible bin, else the first free slot, else slot
    0 with ``no_free`` set), matching ``core.jaxsim._select_slot`` decision
    -for-decision.  Pads the state into kernel layout on every call; hot
    loops should hold their state pre-padded and call
    :func:`fitscore_select_batch_padded` instead.
    """
    L, N, d = loads.shape
    Np, dpad, bn_, nb = select_pad_geometry(N, d, bn)
    f32, i32 = jnp.float32, jnp.int32
    loads_p = jnp.zeros((L, Np, dpad), f32).at[:, :N, :d].set(
        loads.astype(f32))
    counts_p = jnp.zeros((L, Np), i32).at[:, :N].set(counts.astype(i32))
    alive_p = jnp.zeros((L, Np), i32).at[:, :N].set(alive.astype(i32))
    oseq_p = jnp.zeros((L, Np), i32).at[:, :N].set(open_seq.astype(i32))
    aseq_p = jnp.zeros((L, Np), i32).at[:, :N].set(access_seq.astype(i32))
    closes_p = jnp.zeros((L, Np), f32).at[:, :N].set(closes.astype(f32))
    size_p = jnp.zeros((L, dpad), f32).at[:, :d].set(size.astype(f32))
    dmask_p = jnp.zeros((L, dpad), f32).at[:, :d].set(dmask.astype(f32))
    cmask_p = None if cmask is None else \
        jnp.zeros((L, Np), i32).at[:, :N].set(cmask.astype(i32))
    return fitscore_select_batch_padded(
        loads_p, counts_p, alive_p, oseq_p, aseq_p, closes_p, size_p,
        pdep, now, dmask_p, cmask_p, policy=policy, n=N, bn=bn,
        interpret=interpret)
