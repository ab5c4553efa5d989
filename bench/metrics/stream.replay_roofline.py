"""stream.replay_roofline (%): the least time the chip could take for the
window's placement work (``bench/roofline.py``) over the device-busy time
of every operation in the traced window (Pallas kernels and XLA ops
alike).  Layer: replay step and kernels.  Moves stream_events_per_s."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "stream")
