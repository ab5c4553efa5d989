"""sweep.device_idle (%): one minus the union of device operation intervals
over the traced window.  Layer: device.  Moves sweep_events_per_s."""
from bench import roofline


def read(ctx):
    return roofline.idle(ctx, "sweep")
