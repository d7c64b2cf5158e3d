"""Circuit gates per stage of the plan (an exact count): fewer stages
mean fewer rounds of decompress, apply and compress."""


def read(ctx):
    if not ctx.get("n_stages"):
        return None
    return ctx["n_gates"] / ctx["n_stages"]
