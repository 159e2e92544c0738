"""Device busy time per batch in the traced window (ms)."""


def read(ctx):
    t = ctx.traced
    if not t or not t.get("batches") or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / len(t["batches"])
