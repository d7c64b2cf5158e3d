"""Milliseconds the dispatch thread spends staging waves to the device
and dispatching the stage programs and the encode, per stage run in the
window (program span ``pipeline.dispatch``)."""
from span_time import s_per_stage


def read(ctx):
    s = s_per_stage(ctx, "pipeline.dispatch")
    return None if s is None else 1e3 * s
