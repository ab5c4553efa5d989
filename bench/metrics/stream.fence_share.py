"""stream.fence_share (%): the summed ``stream.fence`` spans over the
window: time the host had staged every chunk and waited on the chip for
the stream's last outputs.  Layer: device.  Moves stream_events_per_s."""


def read(ctx):
    if ctx["kind"] != "stream" or ctx["trace"]["dropped"]:
        return None
    durs = [s["dur"] for s in ctx["spans"] if s.get("name") == "stream.fence"]
    if not durs:
        return None
    return 100.0 * sum(durs) / 1e6 / ctx["window_s"]
