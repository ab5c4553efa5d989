"""The cell ``huawei.sweep.hybrid`` (nine d=2 Huawei-East-1 fleets under
Hybrid), cut to test size on the CPU: judged correct through the harness,
driven through ``run_batch`` with the pool size alone, and its records
equal to the plain reference's Hybrid lane for lane, with one altered
record caught; and the reader of ``sweep.step_us``."""
import os

import pytest

from bench import check, harness, reference
from bench.tests.tiny import run, tiny_cell

CELL = "huawei.sweep.hybrid"
SEED = 2 ** 31 + 101


def test_tiny_run_is_judged_correct():
    cell = tiny_cell(CELL)
    assert cell.traffic["policy"] == "hybrid"
    assert cell.config["reduced"] == []
    res = run(cell, seed=SEED)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["bins_off"]["value"] == 0
    assert set(res["metrics"]) == {"setup_s", "sweep_events_per_s"}


def test_tiny_run_passes_only_the_pool_size(monkeypatch):
    import repro.sweep.runner as runner
    real = runner.run_batch
    seen = []

    def spy(*a, **kw):
        seen.append(set(kw))
        return real(*a, **kw)
    monkeypatch.setattr(runner, "run_batch", spy)
    res = run(tiny_cell(CELL), seed=SEED)
    assert res["correct"], res["checks"]
    assert seen and all(kw == {"max_bins"} for kw in seen)


@pytest.mark.parametrize("seed", [SEED, 977])
def test_program_matches_reference_hybrid_record_for_record(seed):
    cell = tiny_cell(CELL)
    drv = cell.runner
    state = drv.setup(cell.config, cell.traffic, seed, False)
    out = drv.call(state, 0)
    keys, tasks = drv.reference_tasks(state, [out])
    assert all(t[0] == "hybrid" for t in tasks)
    assert reference.POLICIES["hybrid"] is reference.Hybrid
    ref = dict(zip(keys, map(reference.replay_task, tasks)))
    assert len(keys) == 2 * len(cell.config["machine_types"])
    for k in keys:
        usage, bins = out["records"][k]
        assert bins == ref[k][1], k
        assert usage == pytest.approx(ref[k][0], rel=1e-6), k
    assert check.compare([out], ref, cell.limits)["correct"]
    u, b = out["records"][keys[-1]]
    out["records"][keys[-1]] = (u * 1.001, b)
    v = check.compare([out], ref, cell.limits)
    assert not v["correct"] and v["failed"] == 1


def _step_us():
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", "sweep.step_us.py"),
        "bench_metric_sweep_step_us")


def _ctx(spans, dropped=False, kind="sweep"):
    return {"kind": kind, "spans": spans, "window_s": 2.0,
            "trace": {"busy_s": 1.3, "window_s": 2.0, "n_ops": 100_000,
                      "dropped": dropped, "device_ops": [], "idle_gaps": []}}


# one call of two rungs: 8,000 steps, then 8,000 again for the lanes that
# overflowed
SCANS = [{"name": "sweep.scan", "ph": "X", "ts": 0.0, "dur": 6e5,
          "args": {"lanes": 18, "steps": 8000}},
         {"name": "sweep.scan", "ph": "X", "ts": 7e5, "dur": 6e5,
          "args": {"lanes": 2, "steps": 8000}},
         {"name": "sweep.run_batch", "ph": "X", "ts": 0.0, "dur": 1.4e6,
          "args": {"events": 150_000}}]


@pytest.mark.parametrize("case", ["value", "steps_absent", "dropped",
                                  "other_kind"])
def test_step_us_reader(case):
    read = _step_us().read
    if case == "value":
        assert read(_ctx(SCANS)) == pytest.approx(1.3e6 / 16_000)
    elif case == "steps_absent":
        # the parent program: its scan spans carry no steps
        old = [dict(s, args={k: v for k, v in s["args"].items()
                             if k != "steps"}) for s in SCANS]
        assert read(_ctx(old)) is None
    elif case == "dropped":
        assert read(_ctx(SCANS, dropped=True)) is None
    else:
        assert read(_ctx(SCANS, kind="stream")) is None
