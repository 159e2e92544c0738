"""Median latency of all requests of the window, from due time (ms)."""
from bench import traffic


def read(ctx):
    return traffic.percentile(traffic.latencies_ms(ctx.window), 50)
