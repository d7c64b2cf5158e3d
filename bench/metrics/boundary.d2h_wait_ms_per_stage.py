"""Milliseconds the dispatch thread spends copying encoded waves from
the device once the device has computed them, per stage run in the
window (program span ``pipeline.d2h_wait``; the wait for the device's
work before the copy is ``pipeline.device_wait``)."""
from span_time import s_per_stage


def read(ctx):
    s = s_per_stage(ctx, "pipeline.d2h_wait")
    return None if s is None else 1e3 * s
