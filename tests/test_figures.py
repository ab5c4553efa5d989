"""Every paper figure runs end to end at test scale and its rows hold.

Each figure of ``benchmarks.figures.ALL_FIGURES`` runs on 2 synthetic
instances of 120 items.  Every row must parse as ``name,us_per_call,
derived  # median= q1= q3=`` under the figure's own prefix, and every
performance ratio (usage time over the Eq.(1) lower bound, a true lower
bound) must be at least 1.
"""
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import figures  # noqa: E402

ROW = re.compile(r"^(?P<name>[^,\s]+),(?P<us>\d+),(?P<mean>[\d.]+)  "
                 r"# median=(?P<median>[\d.]+) q1=(?P<q1>[\d.]+) "
                 r"q3=(?P<q3>[\d.]+)$")


@pytest.fixture(autouse=True)
def _test_scale(monkeypatch):
    monkeypatch.setenv("BENCH_INSTANCES", "2")
    monkeypatch.setenv("BENCH_ITEMS", "120")
    monkeypatch.setenv("BENCH_REPEATS", "1")
    # fig16 replays every scan policy twice, consolidating and not, at
    # about 4 s a policy here: two families (score, category) stand in
    import repro.api
    monkeypatch.setattr(repro.api, "SCAN_POLICIES", ("first_fit", "cbd"))


@pytest.mark.parametrize("fig", figures.ALL_FIGURES,
                         ids=lambda f: f.__name__)
def test_figure_rows(fig):
    rows = fig()
    prefix = fig.__name__.split("_")[0] + "/"
    assert rows
    names = []
    for line in rows:
        m = ROW.match(line)
        assert m, line
        names.append(m["name"])
        assert m["name"].startswith(prefix), line
        q1, median, q3 = (float(m[k]) for k in ("q1", "median", "q3"))
        assert float(m["mean"]) >= 1.0 and q1 >= 1.0, line
        assert q1 <= median <= q3, line
    assert len(set(names)) == len(names), names
