"""Peak bytes of the compressed store (RAM and disk tiers together)
over the amplitudes it holds, 2^n a lane."""


def read(ctx):
    if "peak_total_bytes" not in ctx:
        return None
    return ctx["peak_total_bytes"] / (2 ** ctx["n"] * ctx["lanes"])
