"""The comparison that decides ``correct`` refuses what it must.

The control: the reference itself, put in the program's place and computed
in bfloat16, the precision below the float32 the configurations state,
fails the comparison against the float64 reference.  The faults: a run of
the harness with the timed path broken underneath comes out not correct,
once for each fault a cell of one chip can have (a replay that returns its
state unchanged; half of the lanes left out and filled with the mean of
the rest; one record altered where it is produced)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import check, reference
from bench.tests.tiny import ROOT, run, tiny_cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    CELLS = [w["name"] for w in json.load(fh)["workloads"]]

LIMITS = {"bins_off": 0, "usage_gap": 1e-4}


def _judge(cell, seed, dtype):
    """The cell's lanes (cut to test size) replayed by the reference in
    ``dtype`` and judged against the float64 reference."""
    c = tiny_cell(cell)
    lanes = c.runner.lanes(c.config, c.traffic, seed)
    policy = c.traffic["policy"]
    ref = {k: reference.replay(policy, raw, pd) for k, raw, pd in lanes}
    got = {k: reference.replay(policy, raw, pd, dtype)[:2]
           for k, raw, pd in lanes}
    return check.compare([{"expected": list(got), "records": got}], ref,
                         LIMITS)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 977])
def test_bfloat16_control_fails(cell, seed):
    assert _judge(cell, seed, "float64")["correct"]
    v = _judge(cell, seed, "bfloat16")
    assert not v["correct"]
    assert v["checks"]["usage_gap"]["value"] > 100 * LIMITS["usage_gap"]


def _broken_run_batch(kind):
    import repro.sweep.runner as runner
    real = runner.run_batch

    def broken(*a, **kw):
        res = real(*a, **kw)
        u = np.array(res.usage_time, dtype=float)
        o = np.array(res.n_bins_opened)
        if kind == "unchanged":
            u[:], o[:] = 0.0, 0
        elif kind == "half":
            h = u.shape[0] // 2
            u[h:], o[h:] = u[:h].mean(), int(round(o[:h].mean()))
        else:
            u[0, 0] *= 1.001
        return dataclasses.replace(res, usage_time=u, n_bins_opened=o)
    return runner, broken


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_sweep_faults_come_out_not_correct(monkeypatch, fault):
    mod, broken = _broken_run_batch(fault)
    monkeypatch.setattr(mod, "run_batch", broken)
    res = run(tiny_cell("azure.sweep.bestfit"))
    assert not res["correct"] and res["failed"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_stream_faults_come_out_not_correct(monkeypatch, fault):
    import repro.stream as stream
    real = stream.replay_stream

    def broken(*a, **kw):
        res = real(*a, **kw)
        if fault == "unchanged":
            return dataclasses.replace(res, usage=0.0, opened=0)
        return dataclasses.replace(res, usage=res.usage * 1.001)
    monkeypatch.setattr(stream, "replay_stream", broken)
    res = run(tiny_cell("azure.stream.bestfit"))
    assert not res["correct"] and res["failed"] > 0
