"""The event-blocked replay megakernel vs the per-event reference paths.

Acceptance matrix for ``kernels.fitscore.fitscore_replay_block`` (whole
T-event blocks of the DVBP scan on-chip, carry resident in VMEM):

  * decision-for-decision parity (exact usage totals, bin counts and - via
    simulate() - placements) with the per-event jnp backend for EVERY scan
    policy (all 21: the 8-policy score family plus the 13 category-
    structured names) across clairvoyant / nonclairvoyant-style
    (pdep == arrival) / noisy-predicted rows on mixed-size /
    mixed-dimension padded batches,
  * the T tail block (2n not a multiple of block_events) and non-multiple
    tile geometry (``select_pad_geometry`` with n_slots and d not
    divisible by the kernel tile sizes),
  * the overflow-escalation ladder composing with blocked replay,
  * one-trace-per-geometry jit behavior across grid sweeps that vary
    which instances / how many seed rows fill the lanes, and
  * the per-instance event-sequence content-digest cache.

Instances are fp32-exact (1/64-grid sizes, integer times, power-of-two
noise) so all paths must agree bitwise, not approximately.
"""
import numpy as np
import pytest

from repro.core import Instance
from repro.core.jaxsim import SCAN_POLICIES, simulate
from repro.kernels.fitscore import select_pad_geometry
from repro.sweep import pack_instances, pad_predictions, run_batch


def quantized_instance(seed, n, d):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


@pytest.fixture(scope="module")
def mixed():
    """Mixed item counts AND dimensionality (pad events + dmask), with
    three prediction rows per lane: clairvoyant, pdep == arrival (the
    serving-style nonclairvoyant replay), and power-of-two noise."""
    insts = [quantized_instance(1, 40, 2), quantized_instance(2, 60, 4),
             quantized_instance(3, 30, 3)]
    batch = pack_instances(insts)
    preds = []
    for i in insts:
        rng = np.random.default_rng(100)
        noisy = i.durations * rng.choice([0.25, 0.5, 1.0, 2.0, 4.0],
                                         i.n_items)
        preds.append(np.stack([i.durations, np.zeros(i.n_items), noisy]))
    return insts, batch, pad_predictions(batch, preds)


@pytest.mark.parametrize("policy", SCAN_POLICIES)
def test_blocked_backend_matches_jnp_all_policies(policy, mixed):
    """Every scan policy, every lane, all three information rows: the
    blocked kernel backend (T=16, with a tail block: 120 events per lane)
    is bit-identical to the per-event jnp reference."""
    insts, batch, pdeps = mixed
    a = run_batch(batch, policy, pdeps, max_bins=32, backend="jnp")
    b = run_batch(batch, policy, pdeps, max_bins=32,
                  backend="pallas_interpret", block_events=16)
    assert (a.usage_time == b.usage_time).all(), policy
    assert (a.n_bins_opened == b.n_bins_opened).all(), policy
    assert (a.max_bins == b.max_bins).all(), policy


def test_blocked_matches_perevent_kernel_path(mixed):
    """Blocked and per-event flavors of the SAME kernel backend agree (the
    per-event kernel path is itself proven against jnp and the oracle)."""
    insts, batch, pdeps = mixed
    for policy in ("best_fit_linf", "cbd"):
        a = run_batch(batch, policy, pdeps, max_bins=32,
                      backend="pallas_interpret")
        b = run_batch(batch, policy, pdeps, max_bins=32,
                      backend="pallas_interpret", block_events=8)
        assert (a.usage_time == b.usage_time).all(), policy
        assert (a.n_bins_opened == b.n_bins_opened).all(), policy


def test_blocked_placements_identical():
    """simulate() through the blocked backend: identical placements (the
    strongest decision-for-decision check), tail block included (2n = 60,
    T = 16)."""
    inst = quantized_instance(9, 30, 3)
    noise = inst.durations * np.random.default_rng(4).choice(
        [0.5, 1.0, 2.0], inst.n_items)
    for policy in ("nrt_prioritized", "reduced_hybrid", "ppe_modified",
                   "la_geometric", "adaptive"):
        a = simulate(inst, policy, noise, max_bins=16, backend="jnp")
        b = simulate(inst, policy, noise, max_bins=16,
                     backend="pallas_interpret", block_events=16)
        assert (a.placements == b.placements).all(), policy
        assert a.usage_time == b.usage_time, policy


def test_nonmultiple_tile_geometry():
    """n_slots and d not divisible by the kernel tile sizes: an odd slot
    pool (max_bins=20: Np=20, not a sublane multiple), a pool spanning
    multiple bin tiles with layout padding rows (max_bins=300 -> Np=512),
    and d=5 (dpad=128), on both the per-event and blocked kernel paths."""
    Np, dpad, bn, nb = select_pad_geometry(300, 5)
    assert (Np, dpad, bn, nb) == (512, 128, 256, 2)   # layout padding rows
    assert select_pad_geometry(20, 5)[0] == 20        # odd Np
    insts = [quantized_instance(21, 25, 5), quantized_instance(22, 35, 5)]
    batch = pack_instances(insts)
    for max_bins in (20, 300):
        a = run_batch(batch, "best_fit_linf", max_bins=max_bins,
                      auto_grow=False, backend="jnp")
        for kw in (dict(), dict(block_events=8)):
            b = run_batch(batch, "best_fit_linf", max_bins=max_bins,
                          auto_grow=False, backend="pallas_interpret", **kw)
            assert (a.usage_time == b.usage_time).all(), (max_bins, kw)
            assert (a.n_bins_opened == b.n_bins_opened).all(), (max_bins, kw)


def dense_instance(seed, n, d):
    """High concurrency: many items alive at once, so max_bins=2 overflows
    and the escalation ladder must actually climb."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 2000, n)).astype(float)
    dur = rng.integers(500, 4000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"d{seed}").sorted_by_arrival()


def test_blocked_dense_rcp_conversion_paths():
    """High-concurrency lanes push RCP/PPE through the base-bin-conversion
    and category ON/OFF machinery (and hybrids through threshold
    crossings); the blocked kernel must track the jnp reference exactly
    under heavy load and extreme (0.25x / 4x) prediction noise."""
    insts = [dense_instance(35, 50, 3), dense_instance(36, 60, 2)]
    batch = pack_instances(insts)
    rng = np.random.default_rng(3)
    pdeps = pad_predictions(
        batch, [np.stack([i.durations,
                          i.durations * rng.choice([0.25, 4.0], i.n_items)])
                for i in insts])
    for policy in ("rcp", "ppe_modified", "cbdt", "hybrid_direct_sum"):
        a = run_batch(batch, policy, pdeps, max_bins=32, backend="jnp")
        b = run_batch(batch, policy, pdeps, max_bins=32,
                      backend="pallas_interpret", block_events=8)
        assert (a.usage_time == b.usage_time).all(), policy
        assert (a.n_bins_opened == b.n_bins_opened).all(), policy


def test_blocked_overflow_escalation():
    """The lane-wise slot-pool doubling ladder composes with blocked
    replay: a tiny starting pool converges to the same result."""
    insts = [dense_instance(31, 40, 3), dense_instance(32, 50, 3)]
    batch = pack_instances(insts)
    a = run_batch(batch, "first_fit", max_bins=2, backend="jnp")
    b = run_batch(batch, "first_fit", max_bins=2,
                  backend="pallas_interpret", block_events=8)
    assert not b.overflowed.any() and (b.max_bins > 2).any()
    assert (a.usage_time == b.usage_time).all()
    assert (a.max_bins == b.max_bins).all()


def test_one_trace_across_grid():
    """Grid sweeps that vary which instances / seeds fill the lanes - but
    not the padded geometry (L, n_max, d, max_bins, T) - compile exactly
    once per policy: the jitted replay is keyed on the flattened lane
    layout, not the (B, S) split (regression: 6x2 and 12x1 grids used to
    retrace).  Retraces are read off the ``sweep.jit_trace`` obs counter:
    zero after the first cell is the invariant."""
    from repro import obs
    i6 = [quantized_instance(40 + k, 30, 3) for k in range(6)]
    i12 = [quantized_instance(60 + k, 30, 3) for k in range(12)]
    b6 = pack_instances(i6)
    p6 = pad_predictions(
        b6, [np.stack([i.durations, 2.0 * i.durations]) for i in i6])
    for kw in (dict(backend="jnp"),
               dict(backend="pallas_interpret", block_events=8)):
        c0 = obs.counter_get("sweep.jit_trace")
        run_batch(b6, "greedy", p6, max_bins=64, **kw)       # 6 x 2 lanes
        c1 = obs.counter_get("sweep.jit_trace")
        assert c1 == c0 + 1
        h0 = obs.counter_get("sweep.jit_cache_hit")
        run_batch(pack_instances(i12), "greedy", max_bins=64, **kw)  # 12 x 1
        run_batch(b6, "greedy", p6, max_bins=64, **kw)       # repeat cell
        assert obs.counter_get("sweep.jit_trace") == c1, \
            "same padded geometry must not retrace"
        assert obs.counter_get("sweep.jit_cache_hit") == h0 + 2


def test_event_sequence_digest_cache():
    """pack_instances memoizes the host-side event sort per instance
    *content* digest: repacking the same instances (same or different
    list) is a cache hit; different content is not.  Hit/miss stats live
    on the obs counter registry (``pack.evseq_hit`` / ``pack.evseq_miss``),
    not a module-private dict."""
    from repro import obs
    from repro.sweep import batching
    insts = [quantized_instance(71, 20, 2), quantized_instance(72, 25, 2)]
    pack_instances(insts)
    h0 = obs.counter_get("pack.evseq_hit")
    m0 = obs.counter_get("pack.evseq_miss")
    pack_instances(list(insts))
    assert obs.counter_get("pack.evseq_hit") == h0 + 2
    assert obs.counter_get("pack.evseq_miss") == m0
    other = quantized_instance(73, 20, 2)
    pack_instances([insts[0], other])
    assert obs.counter_get("pack.evseq_hit") == h0 + 3
    assert obs.counter_get("pack.evseq_miss") == m0 + 1
    assert obs.counter_get("pack.evseq_bytes") > 0   # resident-bytes gauge
    # digest covers content, not the name
    renamed = Instance(other.sizes, other.arrivals, other.departures, "x")
    assert batching.instance_digest(renamed) == \
        batching.instance_digest(other)
