"""stream.staging_share (%): the summed ``stream.init``, ``stream.build``,
``stream.put`` and ``stream.step`` spans over the window: the program's
host staging of a stream (builder, carry and row pool of each pass, heap
merge and chunk build, transfer enqueue, step dispatch).  Time the host
spends waiting on the chip is ``stream.fence``, read by
``stream.fence_share``.  Layer: host staging.  Moves
stream_events_per_s."""

STAGING = ("stream.init", "stream.build", "stream.put", "stream.step")


def read(ctx):
    if ctx["kind"] != "stream" or ctx["trace"]["dropped"]:
        return None
    durs = [s["dur"] for s in ctx["spans"] if s.get("name") in STAGING]
    if not durs:
        return None
    return 100.0 * sum(durs) / 1e6 / ctx["window_s"]
