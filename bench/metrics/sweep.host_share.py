"""sweep.host_share (%): one minus the summed ``sweep.scan`` spans (the
program's host span around the device replay, fenced on its results) over
the window: packing, prediction padding, transfer enqueue, readback and
harness time.  Layer: host staging.  Moves sweep_events_per_s."""


def read(ctx):
    if ctx["kind"] != "sweep":
        return None
    scan = [s["dur"] for s in ctx["spans"] if s.get("name") == "sweep.scan"]
    if not scan:
        return None
    return 100.0 * (1.0 - sum(scan) / 1e6 / ctx["window_s"])
