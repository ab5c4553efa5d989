"""repro.consolidate acceptance: MIGRATE events as a scenario axis.

The contract under test (ISSUE 9):

  * the chunked consolidating driver replays MIGRATE streams
    decision-for-decision equal to the sequential consolidating host
    oracle for EVERY scan policy (all 21), on the jnp reference path and
    the event-blocked megakernel (T=1 and T>1) alike,
  * with the axis disabled the sweep is bitwise identical to a build
    without it: same records, same result keys, same spec hashes,
  * ConsolidationSpec parses/round-trips, budgets bound churn, and the
    churn counters (``consolidate.*``) surface through obs,
  * the api facade carries the axis (``Setting.with_consolidation``) and
    ``Experiment.run`` names the failing cell in ``CapacityError``,
  * the serving drain pass executes the same planner's decisions on the
    live carry.

Instances are fp32-exact (1/64-grid sizes, integer times) so the scan's
fp32 usage accumulation must equal the oracle's float64 bitwise.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.consolidate import (ConsolidationSpec, consolidated_replay,
                               plan_migrations, run_consolidating)
from repro.core import Instance
from repro.core.jaxsim import SCAN_POLICIES, host_algorithm
from repro.sweep import (PredModel, SuiteSpec, SweepSpec, SweepStore,
                         pack_instances, run_batch, run_sweep)
from repro.sweep.runner import _flatten_lanes, instances_pdeps

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# underload drain, 8-event planning cadence: dense enough that the
# 40-item streams below plan ~9 times and actually migrate
SPEC = ConsolidationSpec.parse("underload:t0.5:e8")


def qinst(seed, n=40, d=3):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


@pytest.fixture(scope="module")
def pair():
    insts = [qinst(1), qinst(2)]
    return insts, pack_instances(insts)


def _driver(batch, policy, *, backend="jnp", block_events=0, spec=SPEC,
            max_bins=32):
    arrays = (batch.sizes, batch.times, batch.kinds, batch.items,
              instances_pdeps(batch), batch.dmask, batch.arrivals,
              batch.pdeps, batch.n_items)
    flat = _flatten_lanes(*(jnp.asarray(a) for a in arrays))
    return consolidated_replay(*flat, policy=policy, max_bins=max_bins,
                               backend=backend, block_events=block_events,
                               spec=spec)


# ------------------------------------------------------------------- spec

def test_spec_parse_canonical_roundtrip():
    for s in ("none", "underload", "underload:0.4", "underload:0.4:16",
              "underload:t0.25:b64:e128:c0.5", "periodic:100",
              "periodic:dt100:t0.3:b8"):
        spec = ConsolidationSpec.parse(s)
        again = ConsolidationSpec.parse(spec.canonical())
        assert again == spec, s
        assert again.canonical() == spec.canonical() == str(spec)
    assert ConsolidationSpec().canonical() == "none"
    assert not ConsolidationSpec.parse("none").enabled
    p = ConsolidationSpec.parse("periodic:100:0.3:8")
    assert (p.dt, p.threshold, p.budget) == (100.0, 0.3, 8)


def test_spec_rejects_bad_knobs():
    with pytest.raises(AssertionError):
        ConsolidationSpec(kind="defrag")
    with pytest.raises(AssertionError):
        ConsolidationSpec(kind="underload", threshold=0.0)
    with pytest.raises(AssertionError):
        ConsolidationSpec(kind="periodic", dt=0.0)
    with pytest.raises(AssertionError):
        ConsolidationSpec(kind="underload", every=0)


def test_sweep_hash_stable_when_disabled():
    """A spec with the axis off hashes exactly as one predating the axis:
    canonical() must not even mention consolidations."""
    base = SweepSpec(policies=("first_fit",))
    off = SweepSpec(policies=("first_fit",),
                    consolidations=(ConsolidationSpec(),))
    on = SweepSpec(policies=("first_fit",),
                   consolidations=(ConsolidationSpec(), SPEC))
    assert base.spec_hash() == off.spec_hash()
    assert "consolidations" not in base.canonical()
    assert on.spec_hash() != base.spec_hash()
    assert on.canonical()["consolidations"] == [SPEC.canonical()]


# ------------------------------------------- driver vs oracle, all policies

@pytest.mark.parametrize("policy", SCAN_POLICIES)
def test_driver_matches_oracle_all_policies(policy, pair):
    """Every scan policy replays the MIGRATE stream decision-for-decision
    equal to the sequential consolidating host oracle: identical usage
    (fp32-exact instances -> bitwise), bins opened, migration events in
    emission order, and churn stats."""
    insts, batch = pair
    usage, opened, _, over, stats = _driver(batch, policy)
    assert not np.asarray(over).any()
    for lane, inst in enumerate(insts):
        res, ost = run_consolidating(inst, host_algorithm(policy), SPEC)
        assert float(usage[lane]) == res.usage_time, policy
        assert int(opened[lane]) == res.n_bins_opened, policy
        assert stats["events"][lane] == ost["events"], policy
        assert int(stats["migrations"][lane]) == ost["migrations"]
        assert int(stats["bins_closed"][lane]) == ost["bins_closed"]


def test_scenario_actually_migrates(pair):
    """Guard the fixture: the parity above is only meaningful while the
    scenario produces real churn for the score family."""
    _, batch = pair
    *_, stats = _driver(batch, "first_fit")
    assert stats["migrations"].sum() > 0


@pytest.mark.parametrize("policy", SCAN_POLICIES)
def test_driver_blocked_kernel_matches_jnp(policy, pair):
    """The megakernel path replays consolidating streams bit-identically
    to the jnp driver (itself oracle-equal above) at T=1 and T>1."""
    _, batch = pair
    u0, o0, _, _, s0 = _driver(batch, policy)
    for T in (1, 8):
        u, o, _, _, s = _driver(batch, policy, backend="pallas_interpret",
                                block_events=T)
        assert (np.asarray(u) == np.asarray(u0)).all(), (policy, T)
        assert (np.asarray(o) == np.asarray(o0)).all(), (policy, T)
        assert s["events"] == s0["events"], (policy, T)


def test_run_batch_wires_the_driver(pair):
    """run_batch(consolidate=...) surfaces the driver's churn per cell and
    its usage; migration_cost = cost x migrations."""
    _, batch = pair
    spec = ConsolidationSpec.parse("underload:t0.5:e8:c2.5")
    u, _, _, _, stats = _driver(batch, "first_fit", spec=spec)
    res = run_batch(batch, "first_fit", max_bins=32, consolidate=spec)
    assert (res.usage_time[:, 0] == np.asarray(u)).all()
    assert (res.migrations[:, 0] == stats["migrations"]).all()
    assert (res.migration_cost == 2.5 * res.migrations).all()
    base = run_batch(batch, "first_fit", max_bins=32)
    assert base.migrations is None and base.migration_cost is None
    # the drain only executes whole-bin moves that close a bin: usage
    # never increases
    assert (res.usage_time <= base.usage_time).all()
    assert (res.usage_time < base.usage_time).any()


# --------------------------------------------------------- budget + counters

def test_budget_bounds_churn(pair):
    _, batch = pair
    free = _driver(batch, "first_fit",
                   spec=ConsolidationSpec.parse("underload:t0.5:e8"))[4]
    capped = _driver(batch, "first_fit",
                     spec=ConsolidationSpec.parse("underload:t0.5:b1:e8"))[4]
    zero = _driver(batch, "first_fit",
                   spec=ConsolidationSpec.parse("underload:t0.5:b0:e8"))[4]
    assert free["migrations"].sum() > 1
    assert (capped["migrations"] <= 1).all()
    assert capped["budget_exhausted"].sum() > 0
    assert zero["migrations"].sum() == 0


def test_churn_counters_emitted(pair):
    _, batch = pair
    before = {k: obs.counter_get(k) for k in
              ("consolidate.migrations", "consolidate.bins_closed",
               "consolidate.budget_exhausted")}
    *_, stats = _driver(batch, "first_fit")
    assert obs.counter_get("consolidate.migrations") - \
        before["consolidate.migrations"] == stats["migrations"].sum()
    assert obs.counter_get("consolidate.bins_closed") - \
        before["consolidate.bins_closed"] == stats["bins_closed"].sum()
    assert obs.counter_get("consolidate.budget_exhausted") >= \
        before["consolidate.budget_exhausted"]


def test_planner_whole_bin_or_skip():
    """The planner only drains a bin when EVERY item fits somewhere else:
    a candidate with an unplaceable item stays put."""
    loads = np.array([[0.2], [0.9]])
    counts = np.array([1, 1])
    alive = np.array([True, True])
    oseq = np.array([0, 1])
    # bin 0 underloaded but its item (0.2) does not fit in bin 1 (0.9)
    plan = plan_migrations(loads, counts, alive, oseq, {0: [0], 1: [1]},
                           np.array([[0.2], [0.9]]), threshold=0.25)
    assert plan.items == [] and plan.bins_closed == 0
    # with headroom the same bin drains
    plan = plan_migrations(np.array([[0.2], [0.5]]), counts, alive, oseq,
                           {0: [0], 1: [1]}, np.array([[0.2], [0.5]]),
                           threshold=0.25)
    assert plan.items == [0] and plan.bins_closed == 1


# ------------------------------------------------------- sweep grid + store

def test_sweep_grid_consolidation_axis(tmp_path):
    """The grid crosses policies x consolidations; disabled cells write
    the exact legacy records (no ``consolidate`` field, legacy result
    keys), enabled cells append the spec segment and churn fields."""
    spec = SweepSpec(suites=(SuiteSpec("azure", 2, 60, 3),),
                     policies=("first_fit",),
                     predictions=(PredModel("clairvoyant"),),
                     max_bins=32,
                     consolidations=(ConsolidationSpec(),
                                     ConsolidationSpec.parse(
                                         "underload:t0.5:e8")))
    store = SweepStore(str(tmp_path))
    rec = run_sweep(spec, store=store)
    assert len(rec) == 4           # 2 instances x (off, on)
    off = {k: r for k, r in rec.items() if "underload" not in k}
    on = {k: r for k, r in rec.items() if "underload" in k}
    assert len(off) == 2 and len(on) == 2
    for r in off.values():
        assert "consolidate" not in r and "migrations" not in r
    for r in on.values():
        assert r["consolidate"] == "underload:t0.5:b-1:e8"
        assert r["migrations"] >= 0 and r["migration_cost"] == 0.0
        assert r["usage_time"] > 0
    # disabled-path identity: the off cells equal a consolidation-free run
    solo = run_sweep(dataclasses_replace_cons(spec), store=SweepStore(
        str(tmp_path / "solo")))
    assert solo == off


def dataclasses_replace_cons(spec):
    import dataclasses
    return dataclasses.replace(spec, consolidations=(ConsolidationSpec(),))


def test_cli_consolidate_flag(tmp_path):
    """``python -m repro sweep --consolidate`` runs the axis end-to-end and
    persists churn fields in the store."""
    store = str(tmp_path / "store")
    cmd = [sys.executable, "-m", "repro", "sweep", "--suites", "azure",
           "--n-instances", "1", "--n-items", "40",
           "--policies", "first_fit", "--preds", "clairvoyant",
           "--backend", "jnp", "--store", store,
           "--consolidate", "none", "underload:t0.5:e8"]
    env = {**os.environ, "PYTHONPATH": SRC}
    p = subprocess.run(cmd, env=env, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    files = [f for f in os.listdir(store) if f.endswith(".json")
             and f.startswith("sweep_")]
    assert len(files) == 1
    results = json.load(open(os.path.join(store, files[0])))["results"]
    tags = {r.get("consolidate", "none") for r in results.values()}
    assert tags == {"none", "underload:t0.5:b-1:e8"}


# ---------------------------------------------------------------- api facade

def test_setting_consolidation_roundtrip():
    from repro.api import Setting
    s = Setting.clairvoyant().with_consolidation("underload:t0.25")
    assert s.label() == "clairvoyant+underload:t0.25:b-1:e256"
    assert Setting.parse(s.label()) == s
    assert Setting.parse("clairvoyant").consolidation.canonical() == "none"


def test_experiment_consolidation_axis(pair):
    from repro.api import Experiment, Setting, instances
    insts, _ = pair
    base = Setting.clairvoyant()
    cons = base.with_consolidation("underload:t0.5:e8")
    exp = Experiment(instances(insts, name="cons-test"),
                     policies=("first_fit",), settings=(base, cons),
                     max_bins=32)
    res = exp.run()
    settings = {r["setting"] for r in res.rows()}
    assert settings == {base.label(), cons.label()}
    u_base = res.usage_total(setting=base.label())
    u_cons = res.usage_total(setting=cons.label())
    assert 0 < u_cons < u_base


def test_capacity_error_names_failing_cell(pair):
    """Overflow at the escalation cap surfaces as CapacityError naming the
    exact (workload, instance, policy, setting) cell."""
    from repro.api import Experiment, Setting, instances
    from repro.core.jaxsim import CapacityError
    insts, _ = pair
    exp = Experiment(instances(insts, name="tiny-cap"),
                     policies=("first_fit",),
                     settings=(Setting.clairvoyant(),),
                     max_bins=1, max_bins_cap=1)
    with pytest.raises(CapacityError) as ei:
        exp.run()
    msg = str(ei.value)
    for needle in ("tiny-cap", "first_fit", "clairvoyant", "q1"):
        assert needle in msg, (needle, msg)
    assert ei.value.policy == "first_fit"
    assert ei.value.max_bins == 1


# ------------------------------------------------------------------- serving

def test_serving_drain_pass_moves_migrant():
    """BlockDispatcher.consolidate: the planner's drain executes on the
    live carry - the lone occupant of an underloaded replica moves to the
    lowest-open_seq replica with headroom (source excluded), the source
    closes, and the churn stats say exactly that."""
    from repro.serving.dispatch import BlockDispatcher
    from repro.serving.scheduler import ReplicaCapacity, Request
    caps = ReplicaCapacity(slots=4, kv_tokens=1 << 20,
                           prefill_budget=1 << 20)
    disp = BlockDispatcher("first_fit", caps, tps=50.0, max_bins=8,
                           max_items=16, impl="pallas_interpret")
    for rid in range(4):           # fill replica 0 (4 x 0.25 slots)
        disp.enqueue_arrival(Request(rid, float(rid), 64, 64), float(rid))
    disp.enqueue_arrival(Request(4, 4.0, 64, 64), 4.0)   # opens replica 1
    disp.sync()
    assert disp.placements[4] == 1
    for rid in (0, 1):             # replica 0 down to 0.5 slots load
        disp.enqueue_departure(rid, 5.0 + rid)
    disp.sync()
    c0 = obs.counter_get("consolidate.migrations")
    stats = disp.consolidate(8.0, "underload:t0.3")
    assert stats == {"migrations": 1, "bins_closed": 1,
                     "budget_exhausted": 0}
    assert obs.counter_get("consolidate.migrations") == c0 + 1
    assert disp.placements[4] == 0          # drained into replica 0
    assert disp._rid_slot[4] == disp._rid_slot[2]
    assert disp._open_now == 1              # the source replica closed
    # a second pass finds nothing to drain
    assert disp.consolidate(9.0, "underload:t0.3")["migrations"] == 0


def test_serving_drain_respects_budget():
    from repro.serving.dispatch import BlockDispatcher
    from repro.serving.scheduler import ReplicaCapacity, Request
    caps = ReplicaCapacity(slots=4, kv_tokens=1 << 20,
                           prefill_budget=1 << 20)
    disp = BlockDispatcher("first_fit", caps, tps=50.0, max_bins=8,
                           max_items=16, impl="pallas_interpret")
    for rid in range(4):
        disp.enqueue_arrival(Request(rid, float(rid), 64, 64), float(rid))
    for rid in (4, 5):
        disp.enqueue_arrival(Request(rid, 4.0, 64, 64), 4.0)
    disp.sync()
    for rid in range(3):           # replica 0 down to one occupant
        disp.enqueue_departure(rid, 5.0 + rid)
    disp.sync()
    # replicas 0 (0.25) and 1 (0.5): no budget -> no drain, counted
    stats = disp.consolidate(8.0, "underload:t0.3:b0")
    assert stats["migrations"] == 0 and stats["budget_exhausted"] >= 1
