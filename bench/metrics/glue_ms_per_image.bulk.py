"""Device busy time outside the TAOM kernel per image traced (ms): the
im2col, quantize, pads, slot relayout, noise sampling and pools between
kernels."""


def read(ctx):
    t = ctx.traced
    if not t or not t.get("calls") or t["busy_s"] <= 0:
        return None
    return 1e3 * t["glue_s"] / t["images"]
