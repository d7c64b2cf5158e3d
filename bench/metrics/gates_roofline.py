"""Share of the roofline of the gates programs: the least time their work
needs on this chip (``work.py``) over the device time the trace shows,
in percent."""
from layer_time import roofline


def read(ctx):
    return roofline(ctx, "gates")
