"""Streamed chunked replay (repro.stream) vs the in-memory engine.

The contract under test: cutting a request stream into fixed-geometry
chunks, replaying them through the carried scan state over a recycled item
row pool, is *bit-identical* to ``jaxsim.simulate`` on the materialized
instance - usage, opened bins, placements, escalation ladder - for every
policy family, across chunk boundaries that land on MIGRATE events and on
overflow escalations, through pool growth, checkpoint/resume and the
multi-process sweep launcher's store merge.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.api.workload import Setting, stream_source, synthetic
from repro.core import jaxsim
from repro.core.jaxsim import _replay_batch, simulate
from repro.data.traces import _one_instance, load_azure_csv
from repro.kernels.fitscore import (ARRIVAL_KIND, DEPARTURE_KIND,
                                    MIGRATE_KIND)
from repro.resilience.checkpoint import StreamCheckpointer
from repro.stream import (ChunkedWorkload, CsvSource, InstanceSource,
                          chunk_instance_events, replay_chunked_events,
                          replay_stream, synthetic_source)
from repro.sweep import SuiteSpec, SweepSpec, SweepStore, run_sweep

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "azure_packing2020")

# one policy per carry family (score / cbd / hybrid / rcp / la / adaptive)
FAMILY_POLICIES = ("best_fit_l2", "cbd", "hybrid", "rcp", "la_binary",
                   "adaptive")


def _stream_instance(seed=3, n=120, d=4):
    """azure-like synthetic instance, small enough for per-test replay."""
    return _one_instance(seed, n, d, 8, 1800.0, 1.6, f"stream_t{seed}")


def _assert_matches(res, ref, policy):
    assert res.usage == pytest.approx(float(ref.usage_time), rel=0,
                                      abs=0), policy
    assert res.opened == int(ref.n_bins_opened), policy
    assert res.overflow == bool(ref.overflowed), policy
    assert res.max_bins == int(ref.max_bins), policy
    if res.placements is not None:
        assert np.array_equal(res.placements,
                              np.asarray(ref.placements)), policy


# ---------------------------------------------------------------- equality

@pytest.mark.parametrize("policy", FAMILY_POLICIES)
def test_streamed_equals_in_memory_per_family(policy):
    """Chunked streamed replay == simulate, including placements, with a
    pool a fraction of the item count (recycling) for non-hybrid."""
    inst = _stream_instance()
    ref = simulate(inst, policy=policy, max_bins=64)
    res = replay_stream(InstanceSource(inst), policy, chunk_events=32,
                        item_rows=24, max_bins=64,
                        collect_placements=True)
    _assert_matches(res, ref, policy)
    if policy != "hybrid":          # hybrid pins the full table (identity)
        assert res.item_rows < inst.n_items


@pytest.mark.parametrize("chunk_events", (7, 32, 1024))
def test_chunk_geometry_never_changes_results(chunk_events):
    """Any chunk size - smaller than, dividing, or dwarfing the event
    count - produces the same decisions (PAD no-ops + carried state)."""
    inst = _stream_instance(seed=9, n=60)
    ref = simulate(inst, policy="mru", max_bins=64)
    res = replay_stream(InstanceSource(inst), "mru",
                        chunk_events=chunk_events, item_rows=16,
                        max_bins=64, collect_placements=True)
    _assert_matches(res, ref, f"C={chunk_events}")


def test_pool_growth_mid_stream():
    """A pool that starts too small doubles on demand and still replays
    bit-identically (fresh rows are virgin until assigned)."""
    inst = _stream_instance(seed=11, n=200)
    ref = simulate(inst, policy="first_fit", max_bins=64)
    c0 = obs.counter_get("stream.pool_growths")
    res = replay_stream(InstanceSource(inst), "first_fit",
                        chunk_events=64, item_rows=4, max_bins=64,
                        collect_placements=True)
    _assert_matches(res, ref, "grown")
    assert res.item_rows > 4
    assert obs.counter_get("stream.pool_growths") > c0


def test_prefetch_depth_is_execution_only():
    """prefetch=0 (synchronous) and prefetch=3 replay identically."""
    inst = _stream_instance(seed=4, n=80)
    a = replay_stream(InstanceSource(inst), "best_fit_linf",
                      chunk_events=32, item_rows=32, prefetch=0)
    b = replay_stream(InstanceSource(inst), "best_fit_linf",
                      chunk_events=32, item_rows=32, prefetch=3)
    assert (a.usage, a.opened, a.max_bins) == (b.usage, b.opened,
                                               b.max_bins)


def test_kernel_backend_chunked():
    """The event-blocked kernel path (pallas_interpret) streams too:
    chunk_events is a multiple of block_events, carry packed."""
    inst = _stream_instance(seed=6, n=40)
    for policy in ("first_fit", "rcp"):
        ref = simulate(inst, policy=policy, max_bins=32,
                       backend="pallas_interpret", block_events=16)
        res = replay_stream(InstanceSource(inst), policy, chunk_events=16,
                            item_rows=48, max_bins=32,
                            backend="pallas_interpret", block_events=16)
        _assert_matches(res, ref, policy)


# ------------------------------------------------- boundary corner cases

@pytest.mark.parametrize("chunk_events", (8, 9, 10))
def test_migrate_event_across_chunk_boundary(chunk_events):
    """A MIGRATE event adjacent to / exactly on a chunk boundary replays
    like the unchunked migrate-enabled scan (18 events; C=9 puts the
    second MIGRATE as a chunk's last event, C=8 as a chunk's first)."""
    n, d = 8, 3
    rng = np.random.default_rng(0)
    sizes = (rng.integers(1, 24, (n, d)) / 64.0).astype(np.float32)
    arrivals = np.arange(n, dtype=np.float32)
    rdeps = arrivals + np.float32(100.0) + np.arange(n, dtype=np.float32)
    # 8 arrivals, then 2 MIGRATEs at t=10 (items 0, 1 - alive), then deps
    times = np.concatenate([arrivals, [10.0, 10.0], rdeps]).astype(
        np.float32)
    kinds = np.concatenate([np.full(n, ARRIVAL_KIND),
                            [MIGRATE_KIND, MIGRATE_KIND],
                            np.full(n, DEPARTURE_KIND)]).astype(np.int32)
    items = np.concatenate([np.arange(n), [0, 1],
                            np.arange(n)]).astype(np.int32)
    n1 = np.full(1, n, np.int32)
    ref = _replay_batch(sizes[None], times[None], kinds[None], items[None],
                        rdeps[None], None, arrivals[None], rdeps[None], n1,
                        policy="best_fit_l2", max_bins=8, backend="jnp",
                        migrate=True)
    usage, opened, placements, overflow = replay_chunked_events(
        sizes, times, kinds, items, rdeps, arrivals, rdeps,
        policy="best_fit_l2", chunk_events=chunk_events, max_bins=8,
        migrate=True)
    assert usage == np.asarray(ref[0])[0]
    assert opened == np.asarray(ref[1])[0]
    assert np.array_equal(placements, np.asarray(ref[2])[0])
    assert overflow == np.asarray(ref[3])[0]


def test_overflow_rung_on_chunk_boundary():
    """chunk_events=1 puts a boundary after EVERY event - including the
    one that overflows the slot pool - and the escalation ladder restarts
    the stream with a doubled pool, landing on simulate's exact result."""
    inst = _one_instance(3, 40, 4, 8, 860000.0, 0.4, "dense")
    ref = simulate(inst, policy="first_fit", max_bins=4, auto_grow=True)
    assert int(ref.max_bins) > 4    # the instance must actually escalate
    c0 = obs.counter_get("stream.overflow_rungs")
    res = replay_stream(InstanceSource(inst), "first_fit",
                        chunk_events=1, item_rows=64, max_bins=4,
                        collect_placements=True)
    _assert_matches(res, ref, "ladder")
    assert obs.counter_get("stream.overflow_rungs") > c0


@pytest.mark.parametrize("prefetch", (0, 1))
def test_recorded_spans_per_chunk(prefetch):
    """A recorded stream opens one build, put and step span per chunk and
    fences once (and after every chunk when synchronous); the replay span
    carries the events and chunks; recording changes no decision."""
    inst = _stream_instance(seed=5, n=150)
    kw = dict(chunk_events=32, item_rows=32, max_bins=64, prefetch=prefetch,
              collect_placements=True)
    off = replay_stream(InstanceSource(inst), "best_fit_linf", **kw)
    with obs.recording():
        res = replay_stream(InstanceSource(inst), "best_fit_linf", **kw)
        evs = obs.events()
    names = [e["name"] for e in evs]
    assert res.n_chunks > 1
    assert names.count("stream.init") == 1
    for name in ("stream.build", "stream.put", "stream.step"):
        assert names.count(name) == res.n_chunks, name
    assert names.count("stream.fence") == (
        res.n_chunks + 1 if prefetch == 0 else 1)
    builds = [e["args"]["events"] for e in evs
              if e["name"] == "stream.build"]
    assert sum(builds) == res.n_events
    rep, = [e for e in evs if e["name"] == "stream.replay"]
    assert rep["args"]["events"] == res.n_events
    assert rep["args"]["chunks"] == res.n_chunks
    assert (res.usage, res.opened, res.overflow, res.max_bins) == \
        (off.usage, off.opened, off.overflow, off.max_bins)
    assert np.array_equal(res.placements, off.placements)


def test_recorded_events_span_every_overflow_pass():
    """An escalating stream is replayed once per rung: the replay span
    counts the events and chunks of every pass."""
    inst = _one_instance(3, 40, 4, 8, 860000.0, 0.4, "dense")
    with obs.recording():
        res = replay_stream(InstanceSource(inst), "first_fit",
                            chunk_events=16, item_rows=64, max_bins=4)
        evs = obs.events()
    passes = sum(e["name"] == "stream.init" for e in evs)
    assert passes > 1 and res.max_bins > 4
    rep, = [e for e in evs if e["name"] == "stream.replay"]
    assert rep["args"]["events"] == passes * res.n_events
    assert rep["args"]["chunks"] == sum(e["name"] == "stream.step"
                                        for e in evs)


def test_capacity_error_at_cap():
    inst = _one_instance(3, 40, 4, 8, 860000.0, 0.4, "dense")
    with pytest.raises(jaxsim.CapacityError):
        replay_stream(InstanceSource(inst), "first_fit", chunk_events=64,
                      item_rows=64, max_bins=2, max_bins_cap=2)


def test_chunk_builder_validates_order_and_pool():
    src = InstanceSource(_stream_instance(seed=2, n=30))

    class Shuffled:
        def meta(self):
            return src.meta()

        def records(self):
            recs = list(src.records())
            return iter(recs[::-1])

    with pytest.raises(ValueError, match="arrival-sorted"):
        list(ChunkedWorkload(Shuffled(), "first_fit",
                             chunk_events=16, item_rows=8).chunks())
    with pytest.raises(RuntimeError, match="pool exhausted"):
        list(ChunkedWorkload(src, "first_fit", chunk_events=16,
                             item_rows=2, grow=False).chunks())


def test_chunk_instance_events_padding():
    times = np.arange(10, dtype=np.float32)
    kinds = np.ones(10, np.int32)
    items = np.arange(10, dtype=np.int32)
    extra = np.arange(10, dtype=np.int32) * 2
    out = list(chunk_instance_events(times, kinds, items, 4, (extra,)))
    assert len(out) == 3 and out[-1][-1] and not out[0][-1]
    t, k, i, (x,), _ = out[-1]
    assert (t.shape, k.shape, i.shape, x.shape) == ((4,),) * 4
    assert list(k) == [1, 1, -1, -1]        # PAD tail
    assert list(x) == [16, 18, 18, 18]      # PADs carry the running extra


# -------------------------------------------------------- sources / API

def test_csv_source_matches_loader():
    """Line-by-line CSV streaming == the materializing loader, and the
    streamed replay of it == simulate on the loaded instance."""
    insts = {i.name: i for i in load_azure_csv(FIXTURE)}
    for pm in (0, 1):
        inst = insts[f"azure_pm{pm}"]       # already arrival-sorted
        src = CsvSource(FIXTURE, machine_id=pm)
        recs = list(src.records())
        assert len(recs) == inst.n_items
        for j, (size, arr, dep, pdep) in enumerate(recs):
            assert np.array_equal(size, inst.sizes[j])
            assert (arr, dep, pdep) == (inst.arrivals[j],
                                        inst.departures[j],
                                        inst.departures[j])
        ref = simulate(inst, policy="best_fit_l2", max_bins=16)
        res = replay_stream(src, "best_fit_l2", chunk_events=4,
                            item_rows=8, max_bins=16)
        assert (res.usage, res.opened) == (float(ref.usage_time),
                                           int(ref.n_bins_opened))


def test_stream_source_settings():
    """api.stream_source bridges workloads: clairvoyant == simulate,
    noisy models thread predicted departures into the stream."""
    wl = synthetic("azure", n_instances=1, n_items=50, seed=7)
    inst = wl.suite().build()[0]
    res = replay_stream(stream_source(wl), "greedy", chunk_events=32,
                        item_rows=16, max_bins=64)
    ref = simulate(inst, policy="greedy", max_bins=64)
    assert (res.usage, res.opened) == (float(ref.usage_time),
                                       int(ref.n_bins_opened))
    noisy = stream_source(wl, 0, Setting.predicted("lognormal", 0.5),
                          seed=3)
    assert any(abs(p - dep) > 1e-9 for (_, _, dep, p) in noisy.records())
    exact = stream_source(wl, inst.name, "nonclairvoyant")
    assert all(p == dep for (_, _, dep, p) in exact.records())


def test_checkpoint_resume_bit_identical(tmp_path):
    """A killed streamed replay resumes from the snapshot and finishes on
    the uninterrupted result (fast-forwarding the host builder)."""
    inst = _stream_instance(seed=13, n=100)
    src = InstanceSource(inst)
    ref = replay_stream(src, "rcp", chunk_events=16, item_rows=32,
                        max_bins=64)

    ck = StreamCheckpointer(str(tmp_path), every_chunks=3, keep=True)
    full = replay_stream(src, "rcp", chunk_events=16, item_rows=32,
                         max_bins=64, checkpointer=ck)
    assert (full.usage, full.opened) == (ref.usage, ref.opened)
    snaps = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert snaps, "keep=True must leave the last periodic snapshot"

    c0 = obs.counter_get("resilience.stream_ckpt_resume")
    res = replay_stream(src, "rcp", chunk_events=16, item_rows=32,
                        max_bins=64,
                        checkpointer=StreamCheckpointer(
                            str(tmp_path), every_chunks=3))
    assert obs.counter_get("resilience.stream_ckpt_resume") == c0 + 1
    assert res.n_chunks == full.n_chunks    # resumed count includes skips
    assert (res.usage, res.opened, res.max_bins) == (
        ref.usage, ref.opened, ref.max_bins)


# ----------------------------------------------- multi-host sweep launcher

def test_two_host_sweep_merges_to_single_process(tmp_path):
    """Two host slices against one store == the single-process sweep:
    identical records AND identical on-disk checksum."""
    spec = SweepSpec(suites=(SuiteSpec("azure", 2, 60, 5),),
                     policies=("first_fit", "greedy", "cbd", "rcp"),
                     seeds=(0,))
    solo_store = SweepStore(str(tmp_path / "solo"))
    solo = run_sweep(spec, store=solo_store)

    multi_store = SweepStore(str(tmp_path / "multi"))
    for host in (0, 1):
        run_sweep(spec, store=multi_store, host_index=host, host_count=2)
    merged = multi_store.load(spec)
    assert merged == solo
    import json
    with open(solo_store.path(spec)) as f:
        a = json.load(f)
    with open(multi_store.path(spec)) as f:
        b = json.load(f)
    assert a["checksum"] == b["checksum"]
    assert a["results"] == b["results"]


def test_host_slices_are_disjoint_and_complete(tmp_path):
    """Each host computes a strict subset; the union covers the grid."""
    spec = SweepSpec(suites=(SuiteSpec("azure", 1, 40, 5),),
                     policies=("first_fit", "greedy", "mru"), seeds=(0,))
    parts = []
    for host in (0, 1, 2):
        store = SweepStore(str(tmp_path / f"h{host}"))
        parts.append(run_sweep(spec, store=store, host_index=host,
                               host_count=3))
    keys = [set(p) for p in parts]
    assert sum(len(k) for k in keys) == len(set().union(*keys))
    full = run_sweep(spec, store=None)
    assert set().union(*keys) == set(full)
    union = {}
    for p in parts:
        union.update(p)
    assert union == full


# ------------------------------------------- sharded-lane padding (pad > L)

_PAD_SCRIPT = """
import jax, numpy as np
assert jax.local_device_count() == 5, jax.local_device_count()
from repro.core import Instance
from repro.sweep import pack_instances, run_batch
rng = np.random.default_rng(1)
insts = []
for s in range(2):    # L=2 lanes over 5 devices -> pad=3 > L (wrap twice)
    n = 30 + 10 * s
    sizes = rng.integers(1, 24, (n, 3)) / 64.0
    arr = np.sort(rng.integers(0, 5000, n)).astype(float)
    dur = rng.integers(10, 500, n).astype(float)
    insts.append(Instance(sizes, arr, arr + dur, f"p{s}").sorted_by_arrival())
batch = pack_instances(insts)
a = run_batch(batch, "best_fit_l1", max_bins=16, shard="never")
b = run_batch(batch, "best_fit_l1", max_bins=16, shard="always")
assert (a.usage_time == b.usage_time).all()
assert (a.n_bins_opened == b.n_bins_opened).all()
# ndev > 2L: padding must tile ceil(total/L) = 3 copies, not assume 2
solo = pack_instances(insts[:1])
a = run_batch(solo, "first_fit", max_bins=16, shard="never")
b = run_batch(solo, "first_fit", max_bins=16, shard="always")
assert (a.usage_time == b.usage_time).all()
print("PAD-OK")
"""


def test_lane_padding_when_devices_dwarf_lanes():
    """Regression for ``_run_arrays``: 5 forced host devices over 1-2
    lanes (pad > L) must wrap-replicate, not truncate.  Subprocess because
    device count is fixed at jax init."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=5")
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _PAD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PAD-OK" in proc.stdout


# ------------------------------------------------------------ bench gate

def test_stream_smoke_matches_simulate():
    """The CI smoke lane's gate: a 3k-item (6k-event) stream replays
    bit-identically with a bounded pool (the perf/stream_replay_6k row
    asserts exactly this before timing)."""
    src = synthetic_source(3000, seed=17)
    inst = src.inst
    ref = simulate(inst, policy="first_fit", max_bins=128)
    res = replay_stream(src, "first_fit", chunk_events=1024, item_rows=256,
                        max_bins=128)
    assert res.usage == float(ref.usage_time)
    assert res.opened == int(ref.n_bins_opened)
    assert res.item_rows < inst.n_items     # bounded pool actually bounded
    assert res.n_events == 6000
