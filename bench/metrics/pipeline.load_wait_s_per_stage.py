"""Seconds the dispatch thread waits on the load pool for a fetched and
inflated wave, per stage run in the window (program span
``pipeline.load_wait``)."""
from span_time import s_per_stage


def read(ctx):
    return s_per_stage(ctx, "pipeline.load_wait")
