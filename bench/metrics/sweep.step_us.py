"""sweep.step_us (us/step): the traced window's device busy time over the
event-axis length its scans ran (the ``steps`` of its ``sweep.scan`` spans,
padding included, summed over calls and overflow rungs): the device cost of
one step of the lane-batched replay, which cells of different lane counts
share.  Layer: replay step and kernels.  Moves sweep_events_per_s."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "sweep" or tr["dropped"] or not tr["n_ops"]:
        return None
    steps = sum(s.get("args", {}).get("steps", 0) for s in ctx["spans"]
                if s.get("name") == "sweep.scan")
    if not steps:
        return None
    return 1e6 * tr["busy_s"] / steps
