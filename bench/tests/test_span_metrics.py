"""The readers of the program's spans and event counts, on hand-built
contexts, and through a traced tiny run on the CPU."""
import os

import pytest

from bench import harness
from bench.tests.tiny import run, tiny_cell


def _span(name, dur_us, **args):
    ev = {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us)}
    if args:
        ev["args"] = args
    return ev


STREAM_SPANS = [
    _span("stream.replay", 2e6, events=40_000, chunks=20),
    _span("stream.init", 10_000, item_rows=8192, max_bins=512),
    _span("stream.build", 50_000, events=2048),
    _span("stream.build", 30_000, events=2048),
    _span("stream.put", 5_000),
    _span("stream.step", 5_000),
    _span("stream.fence", 1.5e6),
]
SWEEP_SPANS = [
    _span("sweep.run_batch", 8e6, events=560_000, B=28, S=2),
    _span("sweep.flatten", 1_000),
    _span("sweep.scan", 7.9e6, lanes=56),
]

# name: (kind, spans, spans the reader needs, value at window 2 s, 1e6 ops)
READERS = {
    "stream.staging_share": ("stream", STREAM_SPANS,
                             ("stream.init", "stream.build", "stream.put",
                              "stream.step"), 5.0),
    "stream.fence_share": ("stream", STREAM_SPANS, ("stream.fence",), 75.0),
    "stream.ops_per_event": ("stream", STREAM_SPANS, ("stream.replay",),
                             25.0),
    "sweep.ops_per_event": ("sweep", SWEEP_SPANS, ("sweep.run_batch",),
                            1e6 / 560_000),
}


def _ctx(kind, spans, dropped=False):
    return {"kind": kind, "spans": spans, "window_s": 2.0,
            "trace": {"busy_s": 1.9, "window_s": 2.0, "n_ops": 1_000_000,
                      "dropped": dropped, "device_ops": [], "idle_gaps": []}}


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("case", ["value", "other_kind", "dropped",
                                  "spans_absent", "args_absent"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_reader(name, case):
    kind, spans, needed, expected = READERS[name]
    read = _reader(name).read
    other = "sweep" if kind == "stream" else "stream"
    if case == "value":
        assert read(_ctx(kind, spans)) == pytest.approx(expected)
    elif case == "other_kind":
        assert read(_ctx(other, spans)) is None
    elif case == "dropped":
        assert read(_ctx(kind, spans, dropped=True)) is None
    elif case == "spans_absent":
        kept = [s for s in spans if s["name"] not in needed]
        assert read(_ctx(kind, kept)) is None
    else:
        # the parent program: the spans the readers need, without the
        # args this reading adds, or without the new spans at all
        old = [dict(s, args={k: v for k, v in s.get("args", {}).items()
                             if k not in ("events", "chunks")})
               for s in spans if s["name"] in ("stream.replay",
                                               "sweep.run_batch",
                                               "sweep.scan")]
        assert read(_ctx(kind, old)) is None


def test_traced_tiny_stream_reads_its_spans():
    """On the CPU the trace holds no device operation, so only the span
    readers find something; both shares lie inside the window."""
    res = run(tiny_cell("azure.stream.bestfit"), trace=True)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {"stream.staging_share", "stream.fence_share"}
    assert 0 < got["stream.staging_share"] < 100
    assert 0 < got["stream.fence_share"] < 100
    assert got["stream.staging_share"] + got["stream.fence_share"] < 100
