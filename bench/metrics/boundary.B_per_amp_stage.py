"""Bytes across the host-device boundary, both ways, per amplitude and
stage (an exact count of the program's h2d and d2h counters)."""


def read(ctx):
    if "delta" not in ctx or not ctx["jobs"]:
        return None
    moved = ctx["delta"]["h2d_bytes"] + ctx["delta"]["d2h_bytes"]
    return moved / (2 ** ctx["n"] * ctx["lanes"] * ctx["jobs"]
                    * ctx["n_stages"])
