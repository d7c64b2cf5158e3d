"""Seconds the dispatch thread waits at the stage barrier for the store
pool (deflate and put) to finish, per stage run in the window: the
exposed part of the store pool's work (program span
``pipeline.store_wait``)."""
from span_time import s_per_stage


def read(ctx):
    return s_per_stage(ctx, "pipeline.store_wait")
