"""Mean images per batch the micro-batcher formed over the window
(``MicroBatcher.stats()["mean_fill"]``, a program counter)."""


def read(ctx):
    b = ctx.counters.get("batcher")
    if not b or not b["batches_formed"]:
        return None
    return float(b["mean_fill"])
