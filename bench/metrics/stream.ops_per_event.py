"""stream.ops_per_event (ops/event): the device operations of the traced
window over the real events the program replayed in it (the ``events`` of
its ``stream.replay`` spans, every overflow pass included): the per-event
cost of the replay step in launches.  Layer: replay step and kernels.
Moves stream_events_per_s."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "stream" or tr["dropped"] or not tr["n_ops"]:
        return None
    events = sum(s.get("args", {}).get("events", 0) for s in ctx["spans"]
                 if s.get("name") == "stream.replay")
    if not events:
        return None
    return tr["n_ops"] / events
