"""Host time of the micro-batcher's worker per batch over the window
(ms): each batch's time from dispatch to its last Future resolved, less
its wait for the device (``MicroBatcher.stats()["batch_host_s_total"]``
over ``batches_formed``, program counters).  A program without the
counter gives no reading."""


def read(ctx):
    b = ctx.counters.get("batcher")
    if not b or "batch_host_s_total" not in b or not b["batches_formed"]:
        return None
    return 1e3 * b["batch_host_s_total"] / b["batches_formed"]
