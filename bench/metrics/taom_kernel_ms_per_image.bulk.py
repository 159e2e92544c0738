"""Device time inside the TAOM kernel's events per image traced (ms)."""


def read(ctx):
    t = ctx.traced
    if not t or not t.get("calls") or t["kernel_s"] <= 0:
        return None
    return 1e3 * t["kernel_s"] / t["images"]
