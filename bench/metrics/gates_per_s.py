"""Circuit gates applied to whole states in the window, over the
window's length (host clock).  A lane of a sweep counts as a state."""


def read(ctx):
    if "n_gates" not in ctx:
        return None
    return ctx["jobs"] * ctx["n_gates"] * ctx["lanes"] / ctx["window_s"]
