"""The comparison that decides ``correct``: every record the timed path
produced against the reference replay of its lane.

Two numbers are compared, each against its limit from
``bench/limits/<cell>.json``:

  * ``bins_off``: records whose bins-opened count differs from the
    reference's (an exact comparison: limit 0);
  * ``usage_gap``: the largest relative gap |usage - ref| / ref of a
    record's usage time.  The program sums usage in float32, so sound runs
    read a small gap; the limit sits between what sound runs and the
    control read (``PERF.md``).

A record that never came, or is not finite, counts as failed with the
gap 1.
"""
from __future__ import annotations

import math


def compare(calls, reference: dict, limits: dict) -> dict:
    """``calls``: the window's call results, each with ``expected`` (the
    keys of the lanes it was asked to replay) and ``records`` (key ->
    (usage, bins)); ``reference``: key -> (usage, bins, peak)."""
    bins_off = 0
    gap = 0.0
    attempted = failed = 0
    for c in calls:
        for key in c["expected"]:
            ru, rb = reference[key][:2]
            attempted += 1
            u, b = c["records"].get(key, (math.nan, math.nan))
            if not (math.isfinite(u) and math.isfinite(b)):
                g, off = 1.0, True
            else:
                g = abs(u - ru) / ru if ru else abs(u - ru)
                off = int(b) != int(rb)
            bins_off += off
            gap = max(gap, g)
            failed += off or g > limits["usage_gap"]
    checks = {"bins_off": {"value": bins_off, "limit": limits["bins_off"]},
              "usage_gap": {"value": gap, "limit": limits["usage_gap"]}}
    correct = (attempted > 0 and failed == 0 and
               all(c["value"] <= c["limit"] for c in checks.values()))
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "checks": checks}
