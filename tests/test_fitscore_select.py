"""The fused Pallas placement kernel vs the inline jnp scan step.

Parity matrix: every jaxsim policy replayed through ``run_batch`` on the
"jnp" and "pallas_interpret" backends over a mixed-size / mixed-dimension
padded batch (the dmask path: zero-padded dims would poison l_inf residuals
if unmasked) with noisy prediction rows - results must be bit-identical,
because the kernel implements the exact same fp32 score/tie-break/free-slot
semantics (instances are fp32-exact: 1/64-grid sizes, integer times).

Plus the tie-break regression: score ties must fall to the earliest-*opened*
bin, not the smallest slot index - the two diverge as soon as a closed slot
is reused.
"""
import subprocess
import sys
from functools import partial

import jax
import numpy as np
import pytest

from repro.core import Instance, get_algorithm, run
from repro.core.jaxsim import POLICIES, _select_slot, simulate
from repro.kernels import fitscore as fk
from repro.sweep import pack_instances, pad_predictions, run_batch


def quantized_instance(seed, n, d):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


@pytest.fixture(scope="module")
def mixed():
    """Mixed item counts AND dimensionality (exercises pad events + dmask),
    with one fp32-exact noisy prediction row per lane."""
    insts = [quantized_instance(1, 60, 2), quantized_instance(2, 100, 4),
             quantized_instance(3, 40, 3)]
    batch = pack_instances(insts)
    preds = []
    for i in insts:
        rng = np.random.default_rng(7)
        noisy = i.durations * rng.choice([0.5, 1.0, 2.0], i.n_items)
        preds.append(np.stack([i.durations, noisy]))
    return insts, batch, pad_predictions(batch, preds)


@pytest.mark.parametrize("policy", POLICIES)
def test_kernel_backend_bit_identical(policy, mixed):
    insts, batch, pdeps = mixed
    a = run_batch(batch, policy, pdeps, max_bins=16, backend="jnp")
    b = run_batch(batch, policy, pdeps, max_bins=16,
                  backend="pallas_interpret")
    assert not a.overflowed.any() and not b.overflowed.any()
    assert (a.usage_time == b.usage_time).all(), policy
    assert (a.n_bins_opened == b.n_bins_opened).all(), policy
    assert (a.max_bins == b.max_bins).all(), policy


def test_kernel_backend_matches_oracle(mixed):
    """Transitivity anchor: the kernel path equals the Python oracle, not
    just the jnp twin (one policy per score structure)."""
    insts, batch, pdeps = mixed
    for policy in ("best_fit_linf", "nrt_prioritized"):
        res = run_batch(batch, policy, pdeps, max_bins=16,
                        backend="pallas_interpret")
        alg = (get_algorithm("best_fit", norm="linf")
               if policy == "best_fit_linf" else get_algorithm(policy))
        for i, inst in enumerate(insts):
            r = run(inst, alg, predicted_durations=inst.durations)
            assert res.n_bins_opened[i, 0] == r.n_bins_opened, policy
            assert res.usage_time[i, 0] == pytest.approx(r.usage_time,
                                                         abs=1e-3), policy


def test_simulate_kernel_backend_placements(mixed):
    """Single-instance simulate() through the kernel: identical placements
    (the strongest decision-for-decision check)."""
    insts, _, _ = mixed
    for policy in ("first_fit", "best_fit_l2", "greedy"):
        a = simulate(insts[1], policy, max_bins=16, backend="jnp")
        b = simulate(insts[1], policy, max_bins=16,
                     backend="pallas_interpret")
        assert (a.placements == b.placements).all(), policy
        assert a.usage_time == b.usage_time


def select_state(seed, L, d, n, with_cmask):
    """One select's state for ``L`` lanes of ``n`` slots: load rows drawn
    from four per lane (best-fit score ties), open_seq a permutation halved
    (open_seq ties) so opening order disagrees with the slot index,
    access_seq / closes / pdep / now on coarse grids (mru, greedy and nrt
    ties, both gap signs), some lanes with fewer real dims, and the last
    lane full (no feasible slot, no free slot)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 48, (L, 4, d)) / 64.0
    loads = np.take_along_axis(rows, rng.integers(0, 4, (L, n))[:, :, None],
                               axis=1)
    counts = rng.integers(0, 3, (L, n))
    oseq = np.stack([rng.permutation(n) // 2 for _ in range(L)])
    aseq = rng.integers(0, n // 4 + 1, (L, n))
    closes = rng.integers(0, 8, (L, n)) * 100.0
    now = rng.integers(0, 8, L) * 100.0
    pdep = now + rng.integers(-2, 8, L) * 100.0
    size = rng.integers(0, 16, (L, d)) / 64.0
    dmask = np.ones((L, d))
    for lane in range(L):
        real = rng.integers(1, d + 1)
        dmask[lane, real:] = 0.0
        size[lane, real:] = 0.0
        loads[lane, :, real:] = 0.0
    if L > 1:
        counts[-1] = 1
        loads[-1] = 63 / 64
        size[-1, 0] = 2 / 64
    cmask = rng.random((L, n)) > 0.3 if with_cmask else None
    return (loads, counts, counts > 0, oseq, aseq, closes, size, pdep, now,
            dmask, cmask)


@pytest.mark.parametrize("L,d,n", [(1, 1, 20), (3, 2, 300), (8, 5, 512),
                                   (13, 8, 300), (16, 5, 8192)])
@pytest.mark.parametrize("with_cmask", [False, True])
@pytest.mark.parametrize("policy", POLICIES)
def test_select_kernel_matches_select_slot(policy, with_cmask, L, d, n):
    """The lane-dense select kernel against the jnp twin, decision for
    decision: lane counts that are not multiples of 8, pools padded to the
    128-slot layout, d padded to 8 sublanes, and (16 lanes of 8192 slots)
    a grid of two lane blocks."""
    state = select_state(L * 1000 + d * 10 + n, L, d, n, with_cmask)
    want = jax.vmap(partial(_select_slot, policy))(
        *[None if a is None else jax.numpy.asarray(a, dt) for a, dt in
          zip(state, ("float32", "int32", "bool", "int32", "int32",
                      "float32", "float32", "float32", "float32", "float32",
                      "bool"))])
    got = fk.fitscore_select_batch(*state, policy=policy, interpret=True)
    for w, g, what in zip(want, got, ("slot", "found", "no_free")):
        assert (np.asarray(w) == np.asarray(g)).all(), (what, w, g)
    if L > 1:
        assert not got[1][-1] and got[2][-1] and got[0][-1] == 0


def test_select_lanes_per_block():
    """Whole lanes per block: every lane when their loads fit the 2 MiB
    budget, else the largest fitting multiple of 8 that divides L."""
    assert fk.select_event_geometry(2048, 5) == (2048, 8)
    assert fk.select_event_geometry(300, 9) == (384, 16)
    assert fk.select_lanes_per_block(28, 2048, 8) == 28
    assert fk.select_lanes_per_block(56, 2048, 8) == 8
    assert fk.select_lanes_per_block(64, 1024, 8) == 64
    assert fk.select_lanes_per_block(96, 1024, 8) == 48
    assert fk.select_lanes_per_block(1, 65536, 8) == 1
    assert fk.select_lanes_per_block(16, 65536, 8) == 8
    assert fk.select_lanes_per_block(13, 65536, 8) == 13


def tie_break_instance():
    """Engineered so a closed slot is reused before a best-fit tie: slot 0
    (reused by C, opening order 2) vs slot 1 (B, opening order 1) tie on the
    residual for D - opening order must win, giving D to B's bin."""
    sizes = np.array([[0.5], [0.625], [0.625], [0.25]])
    arrivals = np.array([0.0, 1.0, 11.0, 12.0])
    departures = np.array([10.0, 100.0, 100.0, 200.0])
    return Instance(sizes, arrivals, departures, "tie")


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("norm", ["l1", "l2", "linf"])
def test_tie_breaks_by_opening_order_not_slot_index(backend, norm):
    inst = tie_break_instance()
    res = simulate(inst, f"best_fit_{norm}", max_bins=4, backend=backend)
    # A->slot0, B->slot1, A departs (slot0 closes), C reuses slot0; D ties
    # between slot0 (open_seq 2) and slot1 (open_seq 1) -> slot1.
    assert list(res.placements) == [0, 1, 0, 1], (backend, norm)
    r = run(inst, get_algorithm("best_fit", norm=norm))
    assert res.usage_time == pytest.approx(r.usage_time, abs=1e-3)
    assert res.n_bins_opened == r.n_bins_opened == 3


def test_zero_padded_dims_dont_poison_linf():
    """A d=1 lane padded into a d=4 batch must replay exactly like its solo
    run: without dmask the padded dims' residual (1.0) would dominate every
    l_inf score and break ties/ordering."""
    lane = tie_break_instance()                      # d=1, tie-sensitive
    wide = quantized_instance(9, 50, 4)              # forces d_max=4
    batch = pack_instances([lane, wide])
    for backend in ("jnp", "pallas_interpret"):
        res = run_batch(batch, "best_fit_linf", max_bins=16, backend=backend)
        solo = run_batch(pack_instances([lane]), "best_fit_linf",
                         max_bins=16, backend=backend)
        assert res.usage_time[0, 0] == solo.usage_time[0, 0], backend
        assert res.n_bins_opened[0, 0] == solo.n_bins_opened[0, 0], backend


_SHARD_SCRIPT = """
import jax, numpy as np
assert jax.local_device_count() == 4, jax.local_device_count()
from repro.core import Instance
from repro.sweep import pack_instances, pad_predictions, run_batch
rng = np.random.default_rng(0)
insts = []
for s in range(6):   # 6 lanes over 4 devices -> pads to 8
    n = 40 + 10 * s
    sizes = rng.integers(1, 24, (n, 3)) / 64.0
    arr = np.sort(rng.integers(0, 5000, n)).astype(float)
    dur = rng.integers(10, 500, n).astype(float)
    insts.append(Instance(sizes, arr, arr + dur, f"s{s}").sorted_by_arrival())
batch = pack_instances(insts)
a = run_batch(batch, "best_fit_linf", max_bins=2, shard="never")
b = run_batch(batch, "best_fit_linf", max_bins=2, shard="always")
assert (a.usage_time == b.usage_time).all()
assert (a.n_bins_opened == b.n_bins_opened).all()
assert (a.max_bins == b.max_bins).all()      # escalation ladder composes
assert not b.overflowed.any() and (b.max_bins > 2).any()
# S>1 prediction rows through the sharded scan (regression: a nested jit in
# the shard_map body used to fail HLO sharding verification)
pdeps = pad_predictions(batch, [np.stack([i.durations, 2.0 * i.durations])
                                for i in insts])
a = run_batch(batch, "greedy", pdeps, max_bins=32, shard="never")
b = run_batch(batch, "greedy", pdeps, max_bins=32, shard="always")
assert a.S == 2 and (a.usage_time == b.usage_time).all()
# B < ndev (regression: lane padding must wrap when pad > B)
solo = pack_instances(insts[:1])
a = run_batch(solo, "first_fit", max_bins=32, shard="never")
b = run_batch(solo, "first_fit", max_bins=32, shard="always")
assert (a.usage_time == b.usage_time).all()
print("SHARD-OK")
"""


def test_sharded_lanes_match_single_device():
    """run_batch sharded over 4 (forced host) devices == single device,
    including the lane-escalation ladder.  Runs in a subprocess because
    device count is fixed at jax init."""
    import os
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD-OK" in proc.stdout
