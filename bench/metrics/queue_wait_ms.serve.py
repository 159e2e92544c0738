"""Mean wait of a request in the micro-batcher's queue over the window,
from its submit to its batch's dispatch (ms): the program's counters
``MicroBatcher.stats()["queue_wait_s_total"]`` over the requests of the
batches formed.  A program without the counter gives no reading."""


def read(ctx):
    b = ctx.counters.get("batcher")
    if not b or "queue_wait_s_total" not in b or not b["requests_batched"]:
        return None
    return 1e3 * b["queue_wait_s_total"] / b["requests_batched"]
