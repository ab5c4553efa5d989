"""The replay's placement work, counted from shapes and the algorithm (not
from HLO, so the count stays put when the implementation changes), and the
least time the chip could take for it.

Operations, elementwise, per lane:

  * an arrival tests feasibility over ``max_bins x d`` (capacity minus load,
    compare: 2 per slot and dimension; combining the dimensions with the
    slot's open bit: 1 per slot) and then runs its policy's select:
      - ``score`` (best fit, l_inf): the leftover and its max over the
        dimensions (2 per slot and dimension), the masked argmin (2 per
        slot);
      - ``hybrid``: the category mask and the first-fit argmin over the
        opening order (3 per slot);
      - ``adaptive``: three selects, prioritized NRT (gap, case test, two
        masked reductions: 5 per slot), Greedy (clamp, argmax: 3 per slot)
        and First Fit (argmin: 2 per slot);
  * a departure updates its bin: ``d`` load subtractions, the count, the
    close test and the usage sum (``d + 3``).

Bytes: the event operands once (time, kind and item: 12 bytes per
lane-event; per item row its ``d`` sizes, arrival and predicted and real
departures, 4 bytes each), plus the carry read and written once per call
(per lane ``max_bins`` slots of ``d`` loads, count, open bit, opening and
access order, close and open time, and one placement per item row, 4 bytes
each).

The peaks are in ``peaks.json``, keyed by ``device_kind``, each with its
source; a device that is not there is an error.
"""
from __future__ import annotations

import json
import os

FEASIBILITY = (2, 1)          # (per slot and dimension, per slot)
SELECT = {"score": (2, 2), "hybrid": (0, 3), "adaptive": (0, 10)}


def work_per_call(w: dict) -> tuple:
    """(operations, bytes) of one call of geometry ``w`` (a runner's
    ``work``: family, lanes, arrivals, departures, max_bins, d, items)."""
    per_dim, per_slot = FEASIBILITY
    s_dim, s_slot = SELECT[w["family"]]
    n, d = w["max_bins"], w["d"]
    arrival = n * d * (per_dim + s_dim) + n * (per_slot + s_slot)
    ops = w["arrivals"] * arrival + w["departures"] * (d + 3)
    events = w["arrivals"] + w["departures"]
    nbytes = (12 * events + 4 * (d + 3) * w["items"] +
              2 * 4 * (w["lanes"] * n * (d + 6) + w["items"]))
    return ops, nbytes


def scale(w: dict, calls: int) -> dict:
    ops, nbytes = work_per_call(w)
    return {"ops": ops * calls, "bytes": nbytes * calls}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_time(ops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def share(ctx: dict, kind: str):
    """Percent of the roofline that the window's device-busy time reaches,
    or None when the cell is not of ``kind`` or the trace saw no device
    operation."""
    tr = ctx["trace"]
    if ctx["kind"] != kind or not tr["busy_s"] or tr["dropped"]:
        return None
    t, bound = least_time(ctx["work"]["ops"], ctx["work"]["bytes"],
                          peaks(ctx["device_kind"]))
    pct = 100.0 * t / ctx["trace"]["busy_s"]
    print(f"# {kind}.replay_roofline: bound by {bound}, least {t} s, "
          f"{ctx['work']['ops']} ops, {ctx['work']['bytes']} bytes, "
          f"busy {ctx['trace']['busy_s']} s", file=ctx["log"])
    return pct


def idle(ctx: dict, kind: str):
    """Percent of the traced window in which no device operation ran."""
    tr = ctx["trace"]
    if ctx["kind"] != kind or not tr["window_s"] or not tr["n_ops"] \
            or tr["dropped"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
