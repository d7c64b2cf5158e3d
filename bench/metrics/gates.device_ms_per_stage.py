"""Device milliseconds of the gates programs per stage run in the window,
from the trace."""
from layer_time import ms_per_stage


def read(ctx):
    return ms_per_stage(ctx, "gates")
