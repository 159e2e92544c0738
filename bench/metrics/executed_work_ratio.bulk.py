"""MACs the dispatched programs performed as laid out, over the logical
MACs of the images they served (x): the engine's program counter
``executed_macs_total`` (chunk slots and block-diagonal zeros included)
over ``images`` times half the published GEMM table's operations per
image.  Both counters cover every call, the warm-up included.  A program
without the counter gives no reading."""
from bench import work


def read(ctx):
    e = ctx.counters.get("engine")
    if not e or "executed_macs_total" not in e or not e["images"]:
        return None
    logical = work.ops_per_image(ctx.gemms) / 2.0 * e["images"]
    return e["executed_macs_total"] / logical
