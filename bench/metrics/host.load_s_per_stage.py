"""Host worker seconds spent fetching and inflating blocks from the
store (the pipeline's load pool, summed over its threads), per stage
run in the window."""


def read(ctx):
    if "delta" not in ctx or not ctx["jobs"]:
        return None
    return ctx["delta"]["t_decompress"] / (ctx["jobs"] * ctx["n_stages"])
