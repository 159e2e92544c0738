"""Share of its roofline the TAOM kernel reached in the traced window (%).

Least time: the logical GEMMs of every call traced (``work.least_time_s``,
from the published GEMM table and the peak table), over the device time
inside the kernel's events."""
from bench import work


def read(ctx):
    t = ctx.traced
    if not t or not t.get("calls") or t["kernel_s"] <= 0:
        return None
    least = sum(work.least_time_s(ctx.gemms, n, ctx.peak)
                for n in t["calls"])
    return 100.0 * least / t["kernel_s"]
