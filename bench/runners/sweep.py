"""Sweep cells: one unit of work is one grid call through the program's
entry point, ``sweep.runner.run_batch``, from the generated instances to
the per-lane records (``usage_time``, ``n_bins_opened``).

Lanes are the fleet's machine types times the traffic's prediction
settings (lane (b, s): instance b under setting s).  Each call packs the
instances (``sweep.batching.pack_instances``; its event-sequence memo hits
after the warm-up, as it does for a user replaying one suite across a
grid), pads the predictions and replays every lane.  It passes only what
the deployment fixes: the instances, the policy, the predictions and the
cluster's slot pool (``max_bins``); no execution option.
"""
from __future__ import annotations

import numpy as np

from bench import gen


def lanes(cfg: dict, traffic: dict, seed: int):
    """[(key, instance, predicted durations or None)] of every lane, in
    lane order; None marks the clairvoyant setting (real durations)."""
    out = []
    for b in range(len(cfg["machine_types"])):
        raw = gen.instance(cfg, b, seed, gen.requests(cfg, b),
                           prefix=cfg["sweep"]["prefix_arrivals"])
        for s, setting in enumerate(traffic["settings"]):
            pdur = None if setting["kind"] == "clairvoyant" else \
                gen.predictions(raw, setting, seed, b, cfg["time_grid_s"])
            out.append(((b, s), raw, pdur))
    return out


def setup(cfg: dict, traffic: dict, seed: int, trace: bool) -> dict:
    from repro.core.types import Instance
    S = len(traffic["settings"])
    ln = lanes(cfg, traffic, seed)
    raws = [raw for (b, s), raw, _ in ln if s == 0]
    pdurs = [np.stack([raw["departures"] - raw["arrivals"] if pd is None
                       else pd for _, raw, pd in ln[b * S:(b + 1) * S]])
             for b in range(len(raws))]
    state = {"policy": traffic["policy"], "family": traffic["family"],
             "max_bins": cfg["sweep"]["max_bins"], "lanes": ln,
             "keys": [key for key, _, _ in ln], "S": S, "pdurs": pdurs,
             "instances": [Instance(r["sizes"], r["arrivals"],
                                    r["departures"], r["name"])
                           for r in raws],
             "d": max(r["sizes"].shape[1] for r in raws),
             "n_max": max(len(r["arrivals"]) for r in raws),
             "events": 2 * sum(len(raw["arrivals"]) for _, raw, _ in ln)}
    call(state, -1)          # compiles, and fills the packing memo
    return state


def call(state: dict, j: int) -> dict:
    from repro.sweep.batching import pack_instances, pad_predictions
    from repro.sweep.runner import run_batch
    batch = pack_instances(state["instances"])
    pdeps = pad_predictions(batch, state["pdurs"])
    res = run_batch(batch, state["policy"], pdeps,
                    max_bins=state["max_bins"])
    usage = np.asarray(res.usage_time)
    opened = np.asarray(res.n_bins_opened)
    records = {(b, s): (float(usage[b, s]), int(opened[b, s]))
               for b in range(usage.shape[0]) for s in range(usage.shape[1])}
    return {"events": state["events"], "expected": state["keys"],
            "records": records}


def reference_tasks(state: dict, calls):
    """Every call replays the same lanes, so one reference replay per lane
    judges the records of every call."""
    return state["keys"], [(state["policy"], raw, pdur, "float64")
                           for _, raw, pdur in state["lanes"]]


def work(state: dict) -> dict:
    arrivals = state["events"] // 2
    return {"family": state["family"], "lanes": len(state["keys"]),
            "arrivals": arrivals, "departures": arrivals,
            "max_bins": state["max_bins"], "d": state["d"],
            "items": len(state["keys"]) * state["n_max"]}


def release(state: dict) -> None:
    state.pop("instances", None)
