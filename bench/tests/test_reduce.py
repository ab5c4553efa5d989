"""The trace reduction on a small synthesised trace (no chip needed)."""
import os

import pytest

from bench import reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.textproto")


def _text():
    with open(DATA) as fh:
        return "".join(line for line in fh if not line.startswith("#"))


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return reduce.reduce(ProfileData.from_text_proto(_text()))


def test_busy_is_the_union_inside_the_window(red):
    assert not red["dropped"]
    assert red["window_s"] == pytest.approx(900e-6)
    assert red["busy_s"] == pytest.approx(250e-6)
    assert red["n_ops"] == 4


def test_device_ops_by_time(red):
    names = [n for n, _ in red["device_ops"]]
    assert names == ["fusion.1 [fusion]", "closed_call.3 [custom-call]",
                     "copy-start.7 [copy-start]"]
    assert red["device_ops"][0][1] == pytest.approx(150e-6)   # clipped
    assert red["device_ops"][2][1] == pytest.approx(50e-6)


def test_idle_gaps_labelled_by_host(red):
    assert red["idle_gaps"] == [
        ["bench.call/pack", pytest.approx(500e-6)],
        ["bench.call", pytest.approx(150e-6)]]


def test_dropped_buffers_are_flagged():
    from jax.profiler import ProfileData
    marker = ('  lines { id: 3 name: "XLA TraceMe" timestamp_ns: 0 events '
              '{ metadata_id: 9 offset_ps: 0 duration_ps: 1 } }\n'
              '  event_metadata { key: 9 value { id: 9 name: '
              '"Trace Buffers Dropped" } }\n')
    text = _text().replace('  lines {\n    id: 2\n', marker +
                           '  lines {\n    id: 2\n', 1)
    assert reduce.reduce(ProfileData.from_text_proto(text))["dropped"]


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData
    empty = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    r = reduce.reduce(empty)
    assert r["n_ops"] == 0 and r["busy_s"] == 0.0


@pytest.mark.parametrize("text,name", [
    ("%fusion.22 = (s32[1,1]{1,0:T(1,128)}, s32[1]{0}) fusion(s32[1,3] %c)",
     "fusion.22 [fusion]"),
    ("%slice.4 = s32[1]{0:T(128)} slice(s32[2]{0} %f), slice={[1:2]}",
     "slice.4 [slice]"),
    ("jit_step(12)", "jit_step(12)"),
])
def test_op_name(text, name):
    assert reduce.op_name(text) == name
