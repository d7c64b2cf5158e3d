"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr["idle_share"] is None or not tr["n_devices"]:
        return None
    return 100.0 * tr["idle_share"]
