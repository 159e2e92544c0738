"""Whole-step share of the chip's peak in an open-loop cell (%): the
logical operations of the images of the batches traced over the traced
window times the peak."""
from bench import work


def read(ctx):
    t = ctx.traced
    if not t or not t.get("batches") or t["busy_s"] <= 0:
        return None
    ops = work.ops_per_image(ctx.gemms) * t["images"]
    return 100.0 * ops / (t["window_s"] * ctx.peak["ops_per_s"])
