"""repro.obs - spans, counters and replay decision traces.

One observability layer for every execution path: host-side **spans**
(wall-clock intervals, Chrome ``trace_event`` shaped) and always-on
**counters** from the collector; per-event **replay traces** emitted by
the batched scan itself (``trace_level`` on ``run_batch`` /
``Experiment.run``); JSONL / Perfetto **exporters**; and ``python -m
repro obs`` to summarize a run log.  A recorded span is also written into
any ``jax.profiler`` trace running around it (a ``TraceAnnotation`` of its
name on the host plane, on the device operations' clock), for a few
microseconds a span; a span that is not recorded costs one flag check and
never touches ``jax``.

Span-name and counter glossaries live in ``sweep/README.md``.  The rules:
counters are always on (single dict upsert); spans are recorded only
under ``obs.enable()`` / ``obs.recording()`` / env ``REPRO_OBS=1`` and
must stay outside jitted computations (a traced body runs once, at trace
time).  Per-event device data never goes through the collector - it rides
out of the scan as stacked outputs (``ReplayTrace``).
"""
from .collector import (HIST_BOUNDS, Span, annotate, counter_add,
                        counter_deltas, counter_get, counter_hist,
                        counter_ops, counters, disable, enable, enabled,
                        events, instant, recording, reset, span, traced)
from .export import (chrome_trace_events, export_jsonl, export_perfetto,
                     read_jsonl, summarize)
from .trace import (ReplayTrace, TraceDivergence, diff_traces, from_scan)

__all__ = [
    "HIST_BOUNDS", "Span", "annotate", "counter_add", "counter_deltas",
    "counter_get", "counter_hist", "counter_ops", "counters", "disable",
    "enable", "enabled", "events", "instant", "recording", "reset", "span",
    "traced",
    "chrome_trace_events", "export_jsonl", "export_perfetto",
    "read_jsonl", "summarize",
    "ReplayTrace", "TraceDivergence", "diff_traces", "from_scan",
]
