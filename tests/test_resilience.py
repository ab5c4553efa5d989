"""Resilience: deterministic fault injection, the retry/degradation
ladder, checkpoint/resume, store recovery, input quarantine and serving
admission hardening.

The chaos contract everything here asserts: injected failures change HOW
a result is computed (slower rung, resumed scan, journal rebuild) but
never WHAT is computed - usage/decisions stay bit-identical to the
fault-free run.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import Instance
from repro.resilience import checkpoint, faults, guard, validate
from repro.resilience.checkpoint import ReplayCheckpointer
from repro.serving.admission import AdmissionQueue
from repro.serving.scheduler import DVBPScheduler, ReplicaCapacity, Request
from repro.sweep import (PredModel, SuiteSpec, SweepSpec, SweepStore,
                         pack_instances, run_batch, run_sweep)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# one scan policy per family: score / cbd / rcp / la / adaptive
FAMILY_POLICIES = ("greedy", "cbd", "rcp", "la_binary", "adaptive")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """No ambient fault plan, no real backoff sleeps, ever."""
    monkeypatch.setenv("REPRO_RESILIENCE_BACKOFF_SCALE", "0")
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    faults.clear()
    yield
    faults.clear()


def quantized_instance(seed=7, n=60, d=3):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


@pytest.fixture(scope="module")
def small_batch():
    return pack_instances([quantized_instance(s) for s in (1, 2, 3)])


# ------------------------------------------------------------ fault plans

def test_fault_spec_arming():
    plan = faults.parse_plan("a.b:error:2:2")
    assert plan.on_call("a.b") is None           # call 1: not armed yet
    assert plan.on_call("a.b").kind == "error"   # call 2 fires
    assert plan.on_call("a.b").kind == "error"   # call 3 fires
    assert plan.on_call("a.b") is None           # count exhausted
    assert plan.calls["a.b"] == 4


def test_fault_spec_glob_and_forever():
    plan = faults.parse_plan("sweep.*:xla:1:0")  # count 0 = forever
    for _ in range(5):
        assert plan.on_call("sweep.scan").kind == "xla"
    assert plan.on_call("store.load") is None


def test_fire_raises_and_counts():
    c0 = obs.counter_get("resilience.fault_oom")
    with faults.injected("x.y:oom"):
        with pytest.raises(faults.InjectedFault, match="RESOURCE_EXHAUSTED"):
            faults.fire("x.y")
    assert obs.counter_get("resilience.fault_oom") == c0 + 1
    faults.fire("x.y")    # plan gone: a no-op


def test_parse_plan_rejects_unknown_kind():
    with pytest.raises(AssertionError):
        faults.parse_plan("a.b:meteor")


# ------------------------------------------------------- guarded dispatch

def test_guarded_call_retries_transient():
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return 7

    c0 = obs.counter_get("resilience.retry")
    assert guard.guarded_call(flaky, site="t", retries=2) == 7
    assert len(attempts) == 3
    assert obs.counter_get("resilience.retry") == c0 + 2


def test_guarded_call_propagates_non_transient():
    def bug():
        raise ValueError("shape mismatch")
    with pytest.raises(ValueError):
        guard.guarded_call(bug, site="t", retries=5)


def test_replay_rungs_ladder_shape():
    labels = [r.label for r in guard.replay_rungs("pallas_interpret", 4, 2)]
    assert labels == ["blocked_sharded", "perevent_sharded", "perevent",
                      "jnp"]
    assert [r.label for r in guard.replay_rungs("jnp", 0, 1)] == ["jnp"]


def test_run_ladder_degrades_and_counts():
    rungs = guard.replay_rungs("pallas_interpret", 4, 1)

    def attempt(rung):
        if rung.block_events:
            raise faults.InjectedFault("INTERNAL: kernel died")
        return rung.label

    c0 = obs.counter_get("resilience.degrade_blocked_perevent")
    rung, out = guard.run_ladder(attempt, rungs, site="t")
    assert (rung.label, out) == ("perevent", "perevent")
    assert obs.counter_get("resilience.degrade_blocked_perevent") == c0 + 1


def test_run_ladder_last_rung_failure_propagates():
    rungs = guard.replay_rungs("jnp", 0, 1)

    def attempt(rung):
        raise faults.InjectedFault("INTERNAL: dead")
    with pytest.raises(faults.InjectedFault):
        guard.run_ladder(attempt, rungs, site="t")


@pytest.mark.parametrize("plan,counter", [
    # blocked megakernel dies once -> per-event kernel serves
    ("sweep.scan:xla:1:1", "resilience.degrade_blocked_perevent"),
    # blocked AND per-event die -> the jnp reference serves
    ("sweep.scan:xla:1:2", "resilience.degrade_pallas_interpret_jnp"),
])
def test_sweep_degradation_bit_identity(small_batch, plan, counter):
    """A degraded dispatch must return the exact usage of the fault-free
    jnp reference: the ladder trades throughput, never results."""
    base = run_batch(small_batch, "greedy", max_bins=64, backend="jnp",
                     shard="never")
    c0 = obs.counter_get(counter)
    with faults.injected(plan):
        res = run_batch(small_batch, "greedy", max_bins=64,
                        backend="pallas_interpret", block_events=4,
                        shard="never")
    assert obs.counter_get(counter) == c0 + 1
    assert np.array_equal(res.usage_time, base.usage_time)
    assert np.array_equal(res.n_bins_opened, base.n_bins_opened)


def test_sweep_transient_oom_retries_same_rung(small_batch):
    base = run_batch(small_batch, "greedy", max_bins=64, backend="jnp",
                     shard="never")
    r0 = obs.counter_get("resilience.retry")
    d0 = obs.counter_get("resilience.degrade_blocked_perevent")
    with faults.injected("sweep.scan:oom:1:1"):
        res = run_batch(small_batch, "greedy", max_bins=64,
                        backend="pallas_interpret", block_events=4,
                        shard="never")
    assert obs.counter_get("resilience.retry") == r0 + 1
    assert obs.counter_get("resilience.degrade_blocked_perevent") == d0
    assert np.array_equal(res.usage_time, base.usage_time)


# --------------------------------------------------------- checkpointing

def test_checkpoint_roundtrip(tmp_path):
    carry = {"a": np.arange(5), "b": (np.ones((2, 3), np.float32), None),
             "c": [np.float64(2.5)]}
    path = str(tmp_path / "c.npz")
    checkpoint.save_checkpoint(path, carry, {"digest": "x", "next_seg": 3})
    loaded, meta = checkpoint.load_checkpoint(path)
    assert meta == {"digest": "x", "next_seg": 3}
    assert np.array_equal(loaded["a"], carry["a"])
    assert isinstance(loaded["b"], tuple) and loaded["b"][1] is None
    assert np.array_equal(loaded["b"][0], carry["b"][0])
    assert isinstance(loaded["c"], list)


def test_checkpoint_tamper_quarantined(tmp_path):
    path = str(tmp_path / "c.npz")
    checkpoint.save_checkpoint(path, {"a": np.arange(8)}, {"digest": "x"})
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF                  # flip a payload byte
    open(path, "wb").write(bytes(blob))
    c0 = obs.counter_get("resilience.ckpt_corrupt")
    assert checkpoint.load_checkpoint(path) is None
    assert obs.counter_get("resilience.ckpt_corrupt") == c0 + 1
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)               # quarantined, not kept


def test_checkpoint_stale_meta_ignored(tmp_path):
    path = str(tmp_path / "c.npz")
    checkpoint.save_checkpoint(path, {"a": np.arange(3)}, {"digest": "x"})
    c0 = obs.counter_get("resilience.ckpt_stale")
    assert checkpoint.load_checkpoint(path, {"digest": "y"}) is None
    assert obs.counter_get("resilience.ckpt_stale") == c0 + 1
    assert os.path.exists(path)                   # stale stays in place


@pytest.mark.parametrize("policy", FAMILY_POLICIES)
def test_checkpointed_replay_bit_identical(small_batch, tmp_path, policy):
    """Segmented checkpointed replay == the unsegmented scan, for one
    policy per family (rcp exercises the full-stream category cumsum)."""
    base = run_batch(small_batch, policy, max_bins=64, backend="jnp",
                     shard="never")
    ckpt = ReplayCheckpointer(str(tmp_path), every_events=16)
    res = run_batch(small_batch, policy, max_bins=64, backend="jnp",
                    shard="never", checkpoint=ckpt, checkpoint_key=policy)
    assert np.array_equal(res.usage_time, base.usage_time)
    assert np.array_equal(res.n_bins_opened, base.n_bins_opened)
    # a completed replay leaves no resume point behind
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".npz")]


def test_interrupt_resume_bit_identical(small_batch, tmp_path):
    """Kill the segmented replay mid-scan (in-process), rerun: it resumes
    from the snapshot and produces the exact fault-free result."""
    base = run_batch(small_batch, "rcp", max_bins=64, backend="jnp",
                     shard="never")
    ckpt = ReplayCheckpointer(str(tmp_path), every_events=16)
    with faults.injected("ckpt.segment:error:3"):
        with pytest.raises(faults.InjectedFault):
            run_batch(small_batch, "rcp", max_bins=64, backend="jnp",
                      shard="never", checkpoint=ckpt, checkpoint_key="k")
    assert [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    c0 = obs.counter_get("resilience.ckpt_resume")
    res = run_batch(small_batch, "rcp", max_bins=64, backend="jnp",
                    shard="never", checkpoint=ckpt, checkpoint_key="k")
    assert obs.counter_get("resilience.ckpt_resume") == c0 + 1
    assert np.array_equal(res.usage_time, base.usage_time)
    assert np.array_equal(res.n_bins_opened, base.n_bins_opened)


def _migrate_stream(n=24, every=8):
    """A flattened single-lane event stream with MIGRATE events spliced
    across checkpoint-segment boundaries: each picks an item alive at its
    splice point, at the clock of the preceding event."""
    from repro.kernels.fitscore import (ARRIVAL_KIND, DEPARTURE_KIND,
                                        MIGRATE_KIND)
    from repro.sweep.runner import _flatten_lanes, instances_pdeps
    batch = pack_instances([quantized_instance(7, n=n)])
    arrays = (batch.sizes, batch.times, batch.kinds, batch.items,
              instances_pdeps(batch), batch.dmask, batch.arrivals,
              batch.pdeps, batch.n_items)
    sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps, n_items = \
        [np.asarray(a) for a in _flatten_lanes(*arrays)]
    alive, live_at = set(), []      # live_at[i] = items alive before event i
    for i in range(2 * n):
        live_at.append(frozenset(alive))
        if kinds[0, i] == ARRIVAL_KIND:
            alive.add(int(items[0, i]))
        elif kinds[0, i] == DEPARTURE_KIND:
            alive.discard(int(items[0, i]))
    cands = [i for i in range(1, 2 * n) if live_at[i]]
    assert len(cands) >= 3, "instance too sparse for a migrate stream"
    picks = sorted({cands[len(cands) // 4], cands[len(cands) // 2],
                    cands[3 * len(cands) // 4]}, reverse=True)
    for k in picks:                 # descending: earlier indices stay valid
        mig = min(live_at[k])
        times = np.insert(times, k, times[0, k - 1], axis=1)
        kinds = np.insert(kinds, k, MIGRATE_KIND, axis=1)
        items = np.insert(items, k, mig, axis=1)
    return (sizes, times, kinds, items, pdeps, dmask, arrivals, rdeps,
            n_items)


@pytest.mark.parametrize("policy", ("first_fit", "rcp"))
def test_checkpointed_migrate_stream_bit_identical(tmp_path, policy):
    """Segmented replay of a MIGRATE-bearing stream == the unsegmented
    scan with the MIGRATE branch compiled in - snapshots taken between
    migrations resume the exact consolidation state."""
    from repro.core.jaxsim import _replay_batch
    arrays = _migrate_stream()
    ref = _replay_batch(*arrays, policy=policy, max_bins=32, backend="jnp",
                        migrate=True)
    ckpt = ReplayCheckpointer(str(tmp_path), every_events=8)
    out = checkpoint.checkpointed_replay(
        arrays, policy=policy, max_bins=32, backend="jnp", block_events=0,
        ckpt=ckpt, key=f"mig-{policy}", migrate=True)
    assert np.array_equal(np.asarray(out[0]), np.asarray(ref[0]))   # usage
    assert np.array_equal(np.asarray(out[1]), np.asarray(ref[1]))   # bins
    assert np.array_equal(np.asarray(out[2]), np.asarray(ref[2]))   # place
    # kill mid-stream, rerun: resumes from the snapshot, bit-identical
    ckpt2 = ReplayCheckpointer(str(tmp_path / "killed"), every_events=8)
    with faults.injected("ckpt.segment:error:3"):
        with pytest.raises(faults.InjectedFault):
            checkpoint.checkpointed_replay(
                arrays, policy=policy, max_bins=32, backend="jnp",
                block_events=0, ckpt=ckpt2, key="kill", migrate=True)
    c0 = obs.counter_get("resilience.ckpt_resume")
    out2 = checkpoint.checkpointed_replay(
        arrays, policy=policy, max_bins=32, backend="jnp", block_events=0,
        ckpt=ckpt2, key="kill", migrate=True)
    assert obs.counter_get("resilience.ckpt_resume") == c0 + 1
    assert np.array_equal(np.asarray(out2[0]), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(out2[2]), np.asarray(ref[2]))


# -------------------------------------------- chaos matrix: kill + resume

def _sweep_cmd(store):
    return [sys.executable, "-m", "repro", "sweep",
            "--suites", "azure", "--n-instances", "2", "--n-items", "50",
            "--policies", ",".join(FAMILY_POLICIES),
            "--preds", "clairvoyant", "--backend", "jnp",
            "--store", store, "--resume", "--checkpoint-every", "16"]


def _sweep_env(fault=""):
    env = {**os.environ, "PYTHONPATH": SRC,
           "REPRO_RESILIENCE_BACKOFF_SCALE": "0"}
    env.pop("REPRO_FAULTS", None)
    if fault:
        env["REPRO_FAULTS"] = fault
    return env


def _store_results(store):
    files = [f for f in os.listdir(store)
             if f.startswith("sweep_") and f.endswith(".json")]
    assert len(files) == 1, files
    return json.load(open(os.path.join(store, files[0])))["results"]


@pytest.fixture(scope="module")
def clean_sweep(tmp_path_factory):
    """The fault-free reference store the killed runs are compared to."""
    store = str(tmp_path_factory.mktemp("clean"))
    p = subprocess.run(_sweep_cmd(store), env=_sweep_env(),
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    return _store_results(store)


@pytest.mark.parametrize("fault", [
    "sweep.group:kill:2",     # die between (suite, policy, pred) groups
    "sweep.group:kill:4",     # ... later in the grid
    "ckpt.segment:kill:7",    # die MID-scan, between carry snapshots
])
def test_killed_sweep_resumes_bit_identical(clean_sweep, tmp_path, fault):
    """SIGKILL the sweep CLI at several boundaries; the resumed run must
    reproduce the fault-free store exactly (group journal + carry
    checkpoints)."""
    store = str(tmp_path / "store")
    p = subprocess.run(_sweep_cmd(store), env=_sweep_env(fault),
                       capture_output=True, text=True)
    assert p.returncode == 137, (p.returncode, p.stdout, p.stderr)
    p = subprocess.run(_sweep_cmd(store), env=_sweep_env(),
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    assert _store_results(store) == clean_sweep


# ------------------------------------------------------- store resilience

@pytest.fixture()
def swept_store(tmp_path):
    spec = SweepSpec(suites=(SuiteSpec("azure", 2, 60, 5),),
                     policies=("first_fit", "greedy"),
                     predictions=(PredModel("clairvoyant"),), max_bins=32)
    store = SweepStore(str(tmp_path))
    rec = run_sweep(spec, store=store)
    assert rec
    return spec, store, rec


def test_store_truncated_main_rebuilt_from_journal(swept_store):
    spec, store, rec = swept_store
    path = store.path(spec)
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) // 2])       # torn write
    c0 = obs.counter_get("store.corrupt")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        rec2 = run_sweep(spec, store=store)
    assert rec2 == rec                                   # journal rebuild
    assert obs.counter_get("store.corrupt") == c0 + 1
    assert os.path.exists(path + ".corrupt")


def test_store_checksum_mismatch_quarantined(swept_store):
    spec, store, rec = swept_store
    path = store.path(spec)
    blob = json.load(open(path))
    key = sorted(blob["results"])[0]
    blob["results"][key]["usage_time"] += 1.0            # bit rot
    json.dump(blob, open(path, "w"))
    with pytest.warns(RuntimeWarning, match="checksum"):
        rec2 = run_sweep(spec, store=store)
    assert rec2 == rec           # the tampered record never surfaces


def test_store_journal_torn_tail_skipped(swept_store):
    spec, store, rec = swept_store
    with open(store.journal_path(spec), "a") as f:
        f.write('{"suites_hash": "dead, torn mid-')     # crash mid-append
    c0 = obs.counter_get("store.journal_skipped")
    assert run_sweep(spec, store=store) == rec
    assert obs.counter_get("store.journal_skipped") == c0 + 1


def test_store_truncate_fault_recovers(tmp_path):
    """The injected torn write (store.save:truncate) on the LAST group's
    main rewrite: the next load quarantines the main file and rebuilds
    every record from the journal."""
    spec = SweepSpec(suites=(SuiteSpec("azure", 2, 60, 5),),
                     policies=("first_fit", "greedy"),
                     predictions=(PredModel("clairvoyant"),), max_bins=32)
    store = SweepStore(str(tmp_path))
    with faults.injected("store.save:truncate:2:1"):    # 2 groups, 2 saves
        rec = run_sweep(spec, store=store)
    with pytest.warns(RuntimeWarning, match="corrupt"):
        rec2 = run_sweep(spec, store=store)
    assert rec2 == rec


# ------------------------------------------------------ serving hardening

def _drive_scheduler(policy="nrt_prioritized", backend="host", n=80):
    caps = ReplicaCapacity(slots=4, kv_tokens=65536, prefill_budget=262144)
    sched = DVBPScheduler(policy, caps, select_backend=backend)
    rng = np.random.default_rng(5)
    live, t, picks = [], 0.0, []
    for rid in range(n):
        t += float(rng.integers(1, 8))
        while live and live[0][0] <= t:
            ft, r = live.pop(0)
            sched.finish(r, ft)
        req = Request(rid, t, int(rng.integers(16, 512)),
                      int(rng.integers(8, 1024)),
                      predicted_decode_len=int(rng.integers(8, 1024)))
        picks.append(sched.place(req, t))
        live.append((t + req.decode_len / 50.0, rid))
        live.sort()
    return picks, sched


def test_serving_select_degrades_to_jnp_same_decisions():
    host, _ = _drive_scheduler(backend="host")
    c0 = obs.counter_get("resilience.degrade_select_kernel_jnp")
    with faults.injected("serving.select:xla:5:1"):
        picks, sched = _drive_scheduler(backend="pallas_interpret")
    assert picks == host          # a degraded select decides identically
    assert obs.counter_get("resilience.degrade_select_kernel_jnp") == c0 + 1


def test_serving_never_stops_placing_under_total_kernel_failure():
    host, _ = _drive_scheduler(backend="host")
    with faults.injected("serving.select:xla:1:0"):     # every select dies
        picks, sched = _drive_scheduler(backend="pallas_interpret")
    assert picks == host          # the host algorithm zoo still places
    assert sched.last_select_backend == "host"
    assert sched.stats.replica_seconds > 0


def test_admission_queue_sheds_on_saturation_and_deadline():
    caps = ReplicaCapacity(slots=4, kv_tokens=65536, prefill_budget=262144)
    q = AdmissionQueue(DVBPScheduler("first_fit", caps),
                       max_pending=4, deadline=1.0, batch_max=2)
    qf0 = obs.counter_get("resilience.shed_queue_full")
    dl0 = obs.counter_get("resilience.shed_deadline")
    reqs = [Request(i, 0.0, 64, 100) for i in range(6)]
    admitted = [q.submit(r, 0.0) for r in reqs]
    assert admitted == [True] * 4 + [False] * 2        # queue saturates
    assert obs.counter_get("resilience.shed_queue_full") == qf0 + 2
    placed = q.drain(0.5)
    assert [rid for rid, _ in placed] == [0, 1]        # batch_max, FIFO
    assert len(q) == 2
    assert q.drain(5.0) == []                          # deadline lapsed
    assert obs.counter_get("resilience.shed_deadline") == dl0 + 2
    assert q.stats.placed == 2 and q.stats.shed == 4
    assert q.stats.submitted == 6


def test_admission_queue_keeps_draining_under_kernel_failure():
    caps = ReplicaCapacity(slots=4, kv_tokens=65536, prefill_budget=262144)
    q = AdmissionQueue(DVBPScheduler(
        "first_fit", caps, select_backend="pallas_interpret"),
        max_pending=16, deadline=100.0, batch_max=16)
    for i in range(8):
        q.submit(Request(i, 0.0, 64, 100), 0.0)
    with faults.injected("serving.select:xla:1:0"):
        placed = q.drain(1.0)
    assert len(placed) == 8       # degraded placement, nothing shed
    assert q.stats.shed == 0


def test_admission_queue_drains_in_deadline_order():
    """take() pops by earliest expiry (submission order breaking ties) -
    a request about to lapse goes before one with slack; expired entries
    shed mid-drain; uniform deadlines degenerate to exact FIFO."""
    q = AdmissionQueue(None, max_pending=16, deadline=5.0, batch_max=16)
    reqs = [Request(i, 0.0, 64, 100) for i in range(6)]
    q.submit(reqs[0], 0.0, deadline=10.0)
    q.submit(reqs[1], 0.0, deadline=3.0)
    q.submit(reqs[2], 0.0, deadline=1.0)
    q.submit(reqs[3], 0.0)                  # queue default: 5.0
    q.submit(reqs[4], 0.0, deadline=3.0)    # ties with rid 1: rid 1 first
    q.submit(reqs[5], 0.0, deadline=0.2)    # already lapsed by drain time
    dl0 = obs.counter_get("resilience.shed_deadline")
    out = [r.rid for r, _ in q.take(0.5)]
    assert out == [2, 1, 4, 3, 0]
    assert obs.counter_get("resilience.shed_deadline") == dl0 + 1
    assert q.stats.shed_deadline == 1 and len(q) == 0
    # uniform deadline == the legacy insertion-order drain, exactly
    q2 = AdmissionQueue(None, max_pending=16, deadline=5.0, batch_max=16)
    for r in reqs:
        q2.submit(r, 0.0)
    assert [r.rid for r, _ in q2.take(0.1)] == [0, 1, 2, 3, 4, 5]


# ------------------------------------------------- validation / quarantine

def test_validate_rows_reasons():
    sizes = np.array([[0.5], [np.nan], [-0.1], [1.5], [0.5], [0.5]])
    arr = np.array([0.0, 1.0, 2.0, 3.0, 4.0, 5.0])
    dep = np.array([10.0, 11.0, 12.0, 13.0, 4.0, 15.0])
    ids = np.array([0, 1, 2, 3, 4, 0])
    rep = validate.validate_rows(sizes, arr, dep, ids)
    assert rep.counts() == {"nan": 1, "nonpos_size": 1, "oversize": 1,
                            "nonpos_duration": 1, "dup_id": 1}
    assert rep.n_bad == 5 and not rep.ok
    assert rep.keep.tolist() == [True, False, False, False, False, False]
    assert "quarantined" in rep.summary()


def test_sanitize_rows_builds_clean_instance():
    sizes = np.array([[0.5], [np.nan], [0.25]])
    arr = np.array([5.0, 1.0, 0.0])
    dep = np.array([10.0, 2.0, 7.0])
    c0 = obs.counter_get("resilience.quarantine_rows")
    inst, rep = validate.sanitize_rows(sizes, arr, dep, name="t")
    assert rep.n_bad == 1
    assert obs.counter_get("resilience.quarantine_rows") == c0 + 1
    assert obs.counter_get("resilience.quarantine_nan") >= 1
    assert inst.n_items == 2
    assert inst.arrivals.tolist() == [0.0, 5.0]        # sorted by arrival
    assert validate.validate_instance(inst).ok


def test_validate_cli_clean_suite():
    # generated suites are well-formed: the CLI returns without raising
    assert validate.main(["--suites", "azure", "--n-instances", "2",
                          "--n-items", "50"]) is None


# ----------------------------------------------------------- obs plumbing

def test_obs_instant_point_events():
    with obs.recording():
        obs.instant("resilience.marker", foo=1)
        evs = [e for e in obs.events()
               if e["name"] == "resilience.marker"]
    assert len(evs) == 1
    assert evs[0]["ph"] == "i" and evs[0]["dur"] == 0.0
    assert evs[0]["args"] == {"foo": 1}
    assert obs.chrome_trace_events(evs)["traceEvents"][0]["ph"] == "i"


@pytest.mark.parametrize("exc,degradable", [
    (jax.errors.JaxRuntimeError("INTERNAL: TPU halted"), True),
    (jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"), False),
    (jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"),
     False),
    (RuntimeError("INTERNAL: pallas XLA"), False),
    (ValueError("block shape not divisible by 8 and 128"), False),
    (faults.InjectedFault("INTERNAL: injected"), True),
])
def test_only_runtime_device_faults_degrade(exc, degradable):
    """A program the compiler refuses is a bug: no rung may hide it."""
    assert guard.is_degradable(exc) is degradable
    if not degradable:
        assert not guard.is_transient(exc)
