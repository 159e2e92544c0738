"""Share of the traced window in which no operation ran on the device
(%): 1 - union of busy intervals / window."""


def read(ctx):
    t = ctx.traced
    if not t or not t.get("calls") or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
