"""The repro.obs layer: spans + counters, replay decision traces, diffing
and exporters.

Acceptance matrix (ISSUE 6):

  * per-event open-bin / usage series from the traced scan match the host
    oracle engine event-for-event, for at least one policy per family
    (score, CBD, RCP, LA, adaptive),
  * ``trace_level=0`` results are bit-identical to ``trace_level=1`` (the
    trace is an extra scan *output*, never an input),
  * ``diff_traces`` pinpoints an injected single-event divergence exactly,
  * a Perfetto export of an Experiment run covers >= 5 span categories,
  * the serving scheduler's select span/counter names the backend that
    actually served the decision,
  * JSONL run logs round-trip and ``python -m repro obs`` summarizes them,
  * the trace module's event-kind constants stay in sync with the kernel's.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro import obs
from repro.core import Instance, run as oracle_run
from repro.core.jaxsim import event_sequence, host_algorithm
from repro.obs.trace import (ARRIVAL_KIND, DEPARTURE_KIND, PAD_KIND,
                             TraceDivergence)
from repro.sweep import pack_instances, pad_predictions, run_batch

# one representative per scan-policy family
FAMILY_POLICIES = ("best_fit_linf", "cbd", "reduced_hybrid", "rcp",
                   "la_binary", "adaptive")


def quantized_instance(seed, n, d):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 24, (n, d)) / 64.0
    arr = np.sort(rng.integers(0, 50000, n)).astype(float)
    dur = rng.integers(10, 5000, n).astype(float)
    return Instance(sizes, arr, arr + dur, f"q{seed}").sorted_by_arrival()


@pytest.fixture(scope="module")
def traced_batch():
    """Mixed sizes/dims, two prediction rows (clairvoyant + power-of-two
    noise) - every lane has pad events and a distinct event tensor row."""
    insts = [quantized_instance(1, 40, 2), quantized_instance(2, 60, 4),
             quantized_instance(3, 30, 3)]
    batch = pack_instances(insts)
    preds = []
    for i in insts:
        rng = np.random.default_rng(100)
        noisy = i.durations * rng.choice([0.25, 0.5, 1.0, 2.0, 4.0],
                                         i.n_items)
        preds.append(np.stack([i.durations, noisy]))
    return insts, preds, batch, pad_predictions(batch, preds)


# --------------------------------------------------------- spans + counters

def test_counters_always_on():
    c0 = obs.counter_get("test.obs.x")
    obs.counter_add("test.obs.x")
    obs.counter_add("test.obs.x", 2.5)
    assert obs.counter_get("test.obs.x") == c0 + 3.5
    before = obs.counters()
    obs.counter_add("test.obs.y", 7)
    assert obs.counter_deltas(before) == {"test.obs.y": 7}


def test_disabled_span_is_shared_noop():
    prev = obs.enabled()
    obs.enable(False)
    try:
        n0 = len(obs.events())
        s1 = obs.span("test.noop", foo=1)
        s2 = obs.span("test.other")
        assert s1 is s2            # the shared null object, zero alloc
        with s1:
            obs.annotate(bar=2)    # no open span: must not raise
        assert len(obs.events()) == n0
    finally:
        obs.enable(prev)


def test_recording_spans_nesting_and_annotate():
    with obs.recording():
        with obs.span("test.outer", a=1):
            with obs.span("test.inner"):
                obs.annotate(hit=True)   # innermost span gets the attr
        evs = [e for e in obs.events() if e["name"].startswith("test.")]
    assert [e["name"] for e in evs] == ["test.inner", "test.outer"]
    inner, outer = evs
    assert inner["cat"] == "test" and inner["args"] == {"hit": True}
    assert outer["args"] == {"a": 1}
    assert outer["dur"] >= inner["dur"] >= 0
    assert outer["ts"] <= inner["ts"]
    assert not obs.enabled() or obs.enabled()  # state restored by context

    @obs.traced("test.deco")
    def f(x):
        return x + 1

    with obs.recording():
        assert f(1) == 2
        assert any(e["name"] == "test.deco" for e in obs.events())


def test_recorded_span_lands_in_the_profiler_trace(tmp_path):
    """A recorded span is written into a running jax.profiler trace, by
    name, on the host plane (the device operations' clock); a span that
    is not recorded is not."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.recording():
            with obs.span("stream.build", events=3):
                pass
        with obs.span("test.unrecorded"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {p.name: {e.name for line in p.lines for e in line.events}
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")}
    assert "stream.build" in host["/host:CPU"]
    assert not any("test.unrecorded" in names for names in host.values())


def test_unrecorded_span_opens_no_annotation(monkeypatch):
    from repro.obs import collector
    opened = []

    class Spy:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(collector, "_TraceAnnotation", Spy)
    prev = obs.enabled()
    obs.enable(False)
    try:
        with obs.span("test.off") as sp:
            assert sp is collector._NULL_SPAN
    finally:
        obs.enable(prev)
    assert opened == []
    with obs.recording():
        with obs.span("test.on"):
            pass
    assert opened == ["test.on"]


@pytest.mark.parametrize("backend,block_events,loads_bytes", [
    ("jnp", 0, 2 * 64 * 3 * 4),                  # (L, max_bins, d)
    ("pallas_interpret", 0, 2 * 8 * 128 * 4),    # (L, dsub, Np)
    ("pallas_interpret", 8, 2 * 64 * 128 * 4)])  # (L, Np, dpad)
def test_run_batch_span_names_the_loads_layout(backend, block_events,
                                               loads_bytes):
    """``sweep.run_batch`` carries the bytes of the slot loads its scan
    carries, which differ by layout: compact on jnp, lane-dense on the
    per-event kernel, 128-wide rows on the event-blocked one."""
    batch = pack_instances([quantized_instance(s, 20, 3) for s in (1, 2)])
    with obs.recording():
        run_batch(batch, "first_fit", max_bins=64, backend=backend,
                  block_events=block_events)
        evs = obs.events()
    rb, = [e for e in evs if e["name"] == "sweep.run_batch"]
    assert rb["args"]["loads_bytes"] == loads_bytes


# category_bytes from the shapes of the layout that runs (L = 2 lanes,
# 20 item rows, d = 3, a 2-slot pool): tag (L, Np) int32 (Np 2 on jnp, 128
# on the per-event kernel), agg (L, 20, 3) f32, ingen (L, 20) bool on the
# per-event paths; hagg (L, 20, 128) f32 on the event-blocked one; nothing
# for the score family.  steps: the 40 events of 20 items, padded to whole
# 16-event blocks (48) on the event-blocked path
@pytest.mark.parametrize("policy,backend,block_events,category_bytes,steps", [
    ("hybrid", "jnp", 0, 2 * 2 * 4 + 2 * 20 * 3 * 4 + 2 * 20, 40),
    ("hybrid", "pallas_interpret", 0, 2 * 128 * 4 + 2 * 20 * 3 * 4 + 2 * 20,
     40),
    ("hybrid", "pallas_interpret", 16, 2 * 20 * 128 * 4, 48),
    ("best_fit_linf", "pallas_interpret", 0, 0, 40)])
def test_run_batch_span_counts_category_state(policy, backend, block_events,
                                              category_bytes, steps):
    """``sweep.run_batch`` carries the bytes of the category state its
    first scan carries, counted before hybrid's 2-slot pool overflows and
    grows; each ``sweep.scan`` (one per rung) carries the padded event
    axis it scanned."""
    batch = pack_instances([quantized_instance(s, 20, 3) for s in (1, 2)])
    with obs.recording():
        run_batch(batch, policy, max_bins=2, backend=backend,
                  block_events=block_events)
        evs = obs.events()
    rb, = [e for e in evs if e["name"] == "sweep.run_batch"]
    assert rb["args"]["category_bytes"] == category_bytes
    scans = [e for e in evs if e["name"] == "sweep.scan"]
    rungs = rb["args"].get("overflow_rungs", 0)
    assert len(scans) == rungs + 1
    assert sum(e["args"]["steps"] for e in scans) == steps * (rungs + 1)
    if policy == "hybrid":
        assert rungs >= 1


def test_kind_constants_match_kernel():
    from repro.kernels import fitscore
    assert ARRIVAL_KIND == fitscore.ARRIVAL_KIND
    assert DEPARTURE_KIND == fitscore.DEPARTURE_KIND
    assert PAD_KIND == fitscore.PAD_KIND


# ----------------------------------------------------------- replay traces

def _oracle_open_bins(inst, policy, pred):
    """Host-oracle reconstruction of the per-event open-bin series (bin
    indices are absolute in the oracle and reused slots in the scan, so
    the comparable series is the open-bin *count* after each event)."""
    r = oracle_run(inst, host_algorithm(policy), predicted_durations=pred)
    t, k, j = event_sequence(inst)
    counts, series = {}, []
    for kind, item in zip(k, j):
        b = r.placements[item]
        if kind == ARRIVAL_KIND:
            counts[b] = counts.get(b, 0) + 1
        else:
            counts[b] -= 1
            if counts[b] == 0:
                del counts[b]
        series.append(len(counts))
    return r, np.array(series)


@pytest.mark.parametrize("policy", FAMILY_POLICIES)
def test_trace_series_matches_host_oracle(policy, traced_batch):
    """Every lane's traced open-bin series equals the oracle engine's,
    event-for-event, and the running usage series ends at the result."""
    insts, preds, batch, pdeps = traced_batch
    res = run_batch(batch, policy, pdeps, max_bins=32, trace_level=1)
    tr = res.trace
    assert tr is not None and tr.policy == policy
    S = 2
    assert tr.L == len(insts) * S
    for bi, inst in enumerate(insts):
        for si in range(S):
            r, oracle_series = _oracle_open_bins(inst, policy,
                                                 preds[bi][si])
            s = tr.series(bi * S + si)
            assert len(s["open_bins"]) == 2 * inst.n_items
            assert (s["open_bins"] == oracle_series).all(), \
                (policy, inst.name, si)
            assert s["usage"][-1] == res.usage_time[bi, si] == r.usage_time
            # arrivals place into a slot; pad events never leak through
            assert (s["slot"][s["kind"] == ARRIVAL_KIND] >= 0).all()
            assert (s["kind"] != PAD_KIND).all()


def test_trace_level0_bit_identical(traced_batch):
    insts, preds, batch, pdeps = traced_batch
    a = run_batch(batch, "best_fit_linf", pdeps, max_bins=32)
    b = run_batch(batch, "best_fit_linf", pdeps, max_bins=32, trace_level=1)
    assert a.trace is None and b.trace is not None
    assert (a.usage_time == b.usage_time).all()
    assert (a.n_bins_opened == b.n_bins_opened).all()
    assert (a.max_bins == b.max_bins).all()


def test_trace_backend_parity(traced_batch):
    """The blocked-kernel path is bypassed under tracing, but the per-event
    kernel backend still traces - and must agree with jnp event-for-event
    (diff_traces returns None)."""
    insts, preds, batch, pdeps = traced_batch
    a = run_batch(batch, "cbd", pdeps, max_bins=32, backend="jnp",
                  trace_level=1)
    b = run_batch(batch, "cbd", pdeps, max_bins=32,
                  backend="pallas_interpret", trace_level=1)
    assert obs.diff_traces(a.trace, b.trace) is None


def test_diff_traces_pinpoints_injected_divergence(traced_batch):
    insts, preds, batch, pdeps = traced_batch
    tr = run_batch(batch, "best_fit_linf", pdeps, max_bins=32,
                   trace_level=1).trace
    assert obs.diff_traces(tr, tr) is None
    # flip one arrival's chosen slot in one lane
    lane = 3
    ev = int(np.where(tr.kinds[lane] == ARRIVAL_KIND)[0][5])
    slot = tr.slot.copy()
    slot[lane, ev] += 1
    mutated = dataclasses.replace(tr, slot=slot)
    d = obs.diff_traces(tr, mutated)
    assert isinstance(d, TraceDivergence)
    assert (d.lane, d.event, d.field) == (lane, ev, "slot")
    assert d.b_value == d.a_value + 1 and d.kind == ARRIVAL_KIND
    assert "slot" in str(d) and f"lane {lane}" in str(d)
    # an earlier structural difference wins over a later decision one
    kinds = tr.kinds.copy()
    kinds[0, 0] = PAD_KIND if kinds[0, 0] != PAD_KIND else ARRIVAL_KIND
    d2 = obs.diff_traces(tr, dataclasses.replace(mutated, kinds=kinds))
    assert (d2.lane, d2.event, d2.field) == (0, 0, "kind")


def test_trace_lane_view(traced_batch):
    insts, preds, batch, pdeps = traced_batch
    tr = run_batch(batch, "rcp", pdeps, max_bins=32, trace_level=1).trace
    one = tr.lane(2)
    assert one.L == 1 and one.E == tr.E and one.S == 1
    assert (one.slot[0] == tr.slot[2]).all()
    assert (one.usage[0] == tr.usage[2]).all()


# --------------------------------------------------- experiment + exporters

def test_experiment_metrics_traces_and_perfetto(tmp_path):
    from repro import api
    from repro.sweep.grid import result_key
    insts = [quantized_instance(81, 12, 2), quantized_instance(82, 15, 2)]
    wl = api.instances(insts, name="obs-exp")
    exp = api.Experiment(wl, policies=("first_fit", "greedy"))
    store = str(tmp_path / "sweeps")
    with obs.recording():
        res = exp.run(store=store)
        events = obs.events()
    # counter deltas of the producing run ride the Results
    assert res.metrics["experiment.cache_miss"] == 2
    assert res.metrics["sweep.scan_calls"] >= 2
    assert res.metrics["sweep.jit_trace"] >= 1
    assert res.metrics["sweep.device_transfer_bytes"] > 0
    assert res.metrics.get("store.save", 0) >= 1
    # second run: fully cached, no scans
    res2 = exp.run(store=store)
    assert res2.metrics["experiment.cache_hit"] == 2
    assert "sweep.scan_calls" not in res2.metrics
    assert res2.records.keys() == res.records.keys()
    # traced run recomputes every cell and returns one trace per record
    res3 = exp.run(store=store, trace_level=1)
    assert set(res3.traces) == set(res3.records)
    key = result_key(wl.suite(), insts[0].name, "greedy",
                     wl.pred_model(api.Setting.clairvoyant()), 0)
    t = res3.traces[key]
    assert t.L == 1
    assert t.usage[0, -1] == res3.records[key]["usage_time"]
    # the recorded spans cover >= 5 categories and export to Perfetto
    cats = {e["cat"] for e in events}
    assert {"experiment", "suite", "sweep", "store", "pack"} <= cats
    out = tmp_path / "trace.json"
    obs.export_perfetto(str(out), events)
    doc = json.loads(out.read_text())
    assert len({e["cat"] for e in doc["traceEvents"]}) >= 5
    assert all({"name", "ph", "ts", "dur", "pid", "tid"} <= e.keys()
               for e in doc["traceEvents"])


def test_jsonl_roundtrip_and_cli(tmp_path, capsys):
    with obs.recording():
        with obs.span("test.io", k="v"):
            pass
        events = obs.events()
    events = [e for e in events if e["name"] == "test.io"]
    obs.counter_add("test.io.counter", 3)
    log = str(tmp_path / "run.obs.jsonl")
    obs.export_jsonl(log, events, {"test.io.counter": 3},
                     meta={"suite": "unit"})
    evs, counters, meta = obs.read_jsonl(log)
    assert [e["name"] for e in evs] == ["test.io"]
    assert evs[0]["args"] == {"k": "v"}
    assert counters == {"test.io.counter": 3}
    assert meta["suite"] == "unit" and meta["schema"] == 1

    from repro.obs.cli import main as obs_cli
    perfetto = str(tmp_path / "t.json")
    assert obs_cli([log, "--perfetto", perfetto]) == 0
    out = capsys.readouterr().out
    assert "test.io" in out and "test.io.counter" in out
    assert "suite=unit" in out
    assert json.loads(open(perfetto).read())["traceEvents"]


# ----------------------------------------------------------------- serving

def _req(rid, decode=800):
    from repro.serving.scheduler import Request
    return Request(rid=rid, arrival=0.0, prompt_len=256, decode_len=decode,
                   predicted_decode_len=decode)


def test_serving_select_reports_backend():
    from repro.serving.scheduler import DVBPScheduler
    host = DVBPScheduler(policy="first_fit", select_backend="host")
    c0 = obs.counter_get("serving.select_host")
    with obs.recording():
        host.place(_req(0), now=0.0)
        evs = [e for e in obs.events() if e["name"] == "serving.select"]
    assert host.last_select_backend == "host"
    assert obs.counter_get("serving.select_host") == c0 + 1
    assert evs[-1]["args"]["backend"] == "host"
    assert evs[-1]["args"]["policy"] == "first_fit"

    dev = DVBPScheduler(policy="first_fit",
                        select_backend="pallas_interpret")
    c0 = obs.counter_get("serving.select_pallas_interpret")
    with obs.recording():
        dev.place(_req(1), now=0.0)
        evs = [e for e in obs.events() if e["name"] == "serving.select"]
    assert dev.last_select_backend == "pallas_interpret"
    assert obs.counter_get("serving.select_pallas_interpret") == c0 + 1
    assert evs[-1]["args"]["backend"] == "pallas_interpret"
    # "auto" off-TPU resolves (and reports) the jnp twin, not "auto"
    import jax
    if jax.default_backend() != "tpu":
        auto = DVBPScheduler(policy="first_fit", select_backend="auto")
        auto.place(_req(2), now=0.0)
        assert auto.last_select_backend == "jnp"
