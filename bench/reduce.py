"""Reduce a profiler trace to device busy and idle time and a breakdown.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData`` (nothing else is needed).  The planes used:

  * device planes (``/device:...``, the CPU's own excluded): the operations
    on their ``XLA Ops`` line, Pallas kernels and XLA ops alike;
  * host planes (``/host:...``): the benchmark's ``bench.window``
    annotation bounds the window; every host event labels the device's idle
    gaps by what the host was doing in them.

``busy_s`` is the union of operation intervals inside the window, averaged
over the devices that ran any; ``window_s`` is the window's length.
``dropped`` is set when the profiler marked a device's trace with
``Trace Buffers Dropped``: operations are missing, and the device readings
are not to be used.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
DROPPED = "Trace Buffers Dropped"
TOP = 10


def _planes(pd):
    dev, host = [], []
    for p in pd.planes:
        if p.name.startswith("/device:") and "CPU" not in p.name:
            dev.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    return dev, host


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


_OPCODE = re.compile(r"[\]})]\s+([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """``fusion.22 [fusion]`` from the HLO text a TPU trace names an
    operation by; other names pass through."""
    if " = " not in text:
        return text
    head, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} [{m.group(1)}]" if m else head.lstrip("%")


def _label(host_events, t: float) -> str:
    """What the host was doing at ``t``: the innermost ``bench.*``
    annotation and the innermost host event of any kind open then."""
    bench, inner = None, None
    for name, s, e in host_events:
        if s <= t <= e:
            if name.startswith("bench.") and \
                    (bench is None or e - s < bench[1]):
                bench = (name, e - s)
            if inner is None or e - s < inner[1]:
                inner = (name, e - s)
    if inner is None:
        return "(no host event)"
    if bench is None or bench[0] == inner[0]:
        return inner[0]
    return f"{bench[0]}/{inner[0]}"


def reduce(pd) -> Dict:
    """Busy and idle time of the ``bench.window`` of ``pd`` (a
    ``ProfileData``), with the top device operations by time and the
    longest idle gaps."""
    dev, host = _planes(pd)
    host_events = [(e.name, e.start_ns, e.end_ns)
                   for p in host for line in p.lines for e in line.events]
    wins = [(s, e) for name, s, e in host_events if name == WINDOW]
    per_plane = []
    dropped = any(e.name == DROPPED for p in dev for line in p.lines
                  if line.name != OPS_LINE for e in line.events)
    for p in dev:
        ops = [(e.name, e.start_ns, e.end_ns)
               for line in p.lines if line.name == OPS_LINE
               for e in line.events]
        if ops:
            per_plane.append(ops)
    if wins:
        lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    elif per_plane:
        lo = min(s for ops in per_plane for _, s, _ in ops)
        hi = max(e for ops in per_plane for _, _, e in ops)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "n_ops": 0,
                "dropped": dropped, "device_ops": [], "idle_gaps": []}
    busy, by_name, gaps, n_ops = [], {}, [], 0
    for k, ops in enumerate(per_plane):
        iv = []
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                iv.append((s, e))
                by_name[name] = by_name.get(name, 0.0) + (e - s)
                n_ops += 1
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        if k == 0:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n_dev = max(len(per_plane), 1)
    by_op: Dict[str, float] = {}
    for name, t in by_name.items():
        by_op[op_name(name)] = by_op.get(op_name(name), 0.0) + t
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {"busy_s": sum(busy) / n_dev / 1e9,
            "window_s": (hi - lo) / 1e9, "n_ops": n_ops,
            "dropped": dropped,
            "device_ops": [[name, t / n_dev / 1e9] for name, t in top_ops],
            "idle_gaps": [[_label(host_events, (s + e) / 2), (e - s) / 1e9]
                          for s, e in top_gaps]}


def reduce_dir(logdir: str) -> Dict:
    """``reduce`` of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce(ProfileData.from_file(max(paths, key=os.path.getmtime)))
