"""Host worker seconds spent deflating blocks and putting them into the
store (the pipeline's store pool, summed over its threads), per stage
run in the window."""


def read(ctx):
    if "delta" not in ctx or not ctx["jobs"]:
        return None
    return ctx["delta"]["t_compress"] / (ctx["jobs"] * ctx["n_stages"])
