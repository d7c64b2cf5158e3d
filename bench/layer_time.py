"""Shared arithmetic of the device-trace readers: a layer's device
seconds in the traced window, per stage, and its roofline share."""


def device_s(ctx, layer):
    tr = ctx.get("trace")
    if tr is None or not ctx["jobs"]:
        return None
    s = tr["layer_s"].get(layer, 0.0)
    return s if s > 0 else None


def ms_per_stage(ctx, layer):
    s = device_s(ctx, layer)
    if s is None:
        return None
    return 1e3 * s / (ctx["jobs"] * ctx["n_stages"])


def roofline(ctx, layer):
    """Least time of the layer's work in the window (``work.py``), over
    the device time its programs took, in percent."""
    s = device_s(ctx, layer)
    if s is None or "work" not in ctx:
        return None
    least = getattr(ctx["work"], layer).least_s * ctx["jobs"]
    return 100.0 * least / s
