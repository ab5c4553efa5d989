"""sweep.ops_per_event (ops/event): the device operations of the traced
window over the real events the program replayed in it (the ``events`` of
its ``sweep.run_batch`` spans, summed over lanes and overflow rungs): the
per-event cost of the lane-batched replay step in launches.  Layer: replay
step and kernels.  Moves sweep_events_per_s."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "sweep" or tr["dropped"] or not tr["n_ops"]:
        return None
    events = sum(s.get("args", {}).get("events", 0) for s in ctx["spans"]
                 if s.get("name") == "sweep.run_batch")
    if not events:
        return None
    return tr["n_ops"] / events
