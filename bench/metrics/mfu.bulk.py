"""Whole-step share of the chip's peak (%): the logical operations of the
images answered in the traced window over the window times the peak."""
from bench import work


def read(ctx):
    t = ctx.traced
    if not t or not t.get("calls") or t["busy_s"] <= 0:
        return None
    ops = work.ops_per_image(ctx.gemms) * t["images"]
    return 100.0 * ops / (t["window_s"] * ctx.peak["ops_per_s"])
