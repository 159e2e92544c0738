"""Share of its roofline the TAOM kernel reached in the traced window of
an open-loop cell (%).

Least time: the logical GEMMs of the images of every batch traced
(``work.least_time_s``, from the published GEMM table and the peak
table; the zero images a batch is padded with are no work), over the
device time inside the kernel's events."""
from bench import work


def read(ctx):
    t = ctx.traced
    if not t or not t.get("batches") or t["kernel_s"] <= 0:
        return None
    least = sum(work.least_time_s(ctx.gemms, n, ctx.peak)
                for n in t["batches"])
    return 100.0 * least / t["kernel_s"]
