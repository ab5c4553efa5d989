"""The harness: every name resolves to its file, no cell passes an
execution option, and without a chip nothing is measured."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.tiny import ROOT, run, tiny_cell

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
EXECUTION = {"backend", "block_events", "chunk_events", "prefetch", "shard",
             "depth", "geometries"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.resolve(ROOT, cell)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c.chips == w["chips"] == 1
    assert os.path.exists(os.path.join(harness.BENCH, "traffic",
                                       w["traffic"] + ".json"))
    assert os.path.exists(os.path.join(harness.BENCH, "limits",
                                       cell + ".json"))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.traffic["rate_metric"] in names
    assert c.per_layer and set(c.readers) == {m["name"] for m in c.per_layer}


def test_spec_names_files_and_shapes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert json.load(fh)["reduced"] == c["reduced"]
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def _keys(obj):
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield k
            yield from _keys(v)
    elif isinstance(obj, list):
        for v in obj:
            yield from _keys(v)


@pytest.mark.parametrize("cell", CELLS)
def test_files_hold_no_execution_option(cell):
    c = harness.resolve(ROOT, cell)
    assert not EXECUTION & set(_keys(c.traffic))
    assert not EXECUTION & set(_keys(c.config))


@pytest.fixture
def recorded(monkeypatch):
    """Keyword arguments each program entry point was called with."""
    import repro.stream
    import repro.sweep.runner
    seen = {}
    for mod, name in ((repro.sweep.runner, "run_batch"),
                      (repro.stream, "replay_stream")):
        real = getattr(mod, name)

        def spy(*a, _real=real, _name=name, **kw):
            seen.setdefault(_name, set()).update(kw)
            return _real(*a, **kw)
        monkeypatch.setattr(mod, name, spy)
    return seen


@pytest.mark.parametrize("cell,entry,allowed", [
    ("azure.sweep.bestfit", "run_batch", {"max_bins"}),
    ("azure.stream.bestfit", "replay_stream", {"max_bins", "item_rows"}),
])
def test_cell_passes_no_execution_option(recorded, cell, entry, allowed):
    res = run(tiny_cell(cell))
    assert res["correct"], res["checks"]
    assert recorded[entry] == allowed


@pytest.mark.parametrize("cell", ["azure.sweep.bestfit",
                                  "azure.stream.bestfit"])
def test_tiny_run_is_judged_correct(cell):
    res = run(tiny_cell(cell))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"bins_off", "usage_gap"}
    assert set(res["metrics"]) == {"setup_s", tiny_cell(cell).traffic[
        "rate_metric"]}


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU found" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
