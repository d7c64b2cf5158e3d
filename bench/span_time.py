"""Shared arithmetic of the program-span readers: a span's seconds in
the window's jobs, per stage, from the program's ring of stage records
(``repro.spans``).

The window's jobs are the last ``ctx["jobs"]`` runs in the ring: the
warm-up job is an earlier run, and the comparison after the window runs
no stage.  A program without stage records reads nothing.
"""


def window_records(ctx):
    """The stage records of the window's jobs, or None."""
    if not ctx.get("jobs"):
        return None
    try:
        from repro.spans import recent
    except ImportError:
        return None
    recs = recent()
    runs = set(sorted({r.run for r in recs})[-ctx["jobs"]:])
    return [r for r in recs if r.run in runs]


def s_per_stage(ctx, name):
    """Seconds of span ``name``, summed over the window's jobs and
    threads, per stage run in the window."""
    recs = window_records(ctx)
    if not recs or not ctx.get("n_stages"):
        return None
    total = sum(r.span_s.get(name, 0.0) for r in recs)
    return total / (ctx["jobs"] * ctx["n_stages"])
