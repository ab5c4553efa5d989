"""The roofline work functions and the peaks table."""
import io

import pytest

from bench import roofline

W = {"family": "score", "lanes": 2, "arrivals": 10, "departures": 10,
     "max_bins": 64, "d": 5, "items": 20}


def test_work_counts_from_shapes():
    ops, nbytes = roofline.work_per_call(W)
    # arrival: 64 x 5 x (2 + 2) + 64 x (1 + 2); departure: 5 + 3
    assert ops == 10 * (64 * 5 * 4 + 64 * 3) + 10 * 8
    # events 12 B, items (d + 3) x 4 B, carry 2 x 4 B x (lanes x 64 x 11 +
    # items)
    assert nbytes == 12 * 20 + 4 * 8 * 20 + 8 * (2 * 64 * 11 + 20)


@pytest.mark.parametrize("family,extra", [("hybrid", 64 * 3),
                                          ("adaptive", 64 * 10)])
def test_families_differ_in_select(family, extra):
    ops, _ = roofline.work_per_call(dict(W, family=family))
    assert ops == 10 * (64 * 5 * 2 + 64 * 1 + extra) + 10 * 8


def test_scale_is_linear():
    one = roofline.scale(W, 1)
    three = roofline.scale(W, 3)
    assert three == {"ops": 3 * one["ops"], "bytes": 3 * one["bytes"]}


def test_v5e_peaks_have_a_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_raises():
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks("cpu")


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_time(1000, 10, peak) == (10.0, "ops")
    assert roofline.least_time(10, 1000, peak) == (100.0, "bytes")


def _ctx(kind, busy, n_ops=5):
    return {"kind": kind, "device_kind": "TPU v5 lite", "log": io.StringIO(),
            "work": roofline.scale(W, 4), "window_s": 2.0,
            "trace": {"busy_s": busy, "window_s": 2.0, "n_ops": n_ops,
                      "dropped": False}}


def test_share_reads_only_its_kind_and_never_zero():
    assert roofline.share(_ctx("stream", 1.0), "sweep") is None
    assert roofline.share(_ctx("sweep", 0.0), "sweep") is None
    v = roofline.share(_ctx("sweep", 1.0), "sweep")
    assert 0.0 < v < 100.0


def test_dropped_trace_reads_nothing():
    ctx = _ctx("sweep", 0.5)
    ctx["trace"]["dropped"] = True
    assert roofline.share(ctx, "sweep") is None
    assert roofline.idle(ctx, "sweep") is None


def test_idle_share():
    assert roofline.idle(_ctx("sweep", 0.5), "sweep") == pytest.approx(75.0)
    assert roofline.idle(_ctx("sweep", 0.5, n_ops=0), "sweep") is None
    assert roofline.idle(_ctx("sweep", 0.5), "stream") is None
