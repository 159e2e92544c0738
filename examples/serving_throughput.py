"""The compiled serving path: plan once, compile once, stream batches.

Demonstrates the executor hot-path fix (ISSUE 2):

1. Plan the small CNN once (content-addressed plan cache).
2. ``compiled_forward`` returns a jit executable with the plan's tilings
   baked in as static args — the first call compiles, every later call
   runs the cached executable: zero retraces, zero per-layer host syncs.
3. Stream a few warm batches and measure sustained images/sec, compiled
   vs the eager op-by-op path the executor used to be.
4. Traces (per-layer numerics fingerprints) are computed on-device and
   materialize lazily — only when actually read, after the stream.

Run:  PYTHONPATH=src python examples/serving_throughput.py
"""
import time

import jax

from repro.core.perf_model import AcceleratorConfig
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, compiled_forward, execute_cnn,
                        plan_for_network, trace_count)
from repro.models.cnn import build_small_cnn

BATCH = 32
STREAM = 8


def main():
    key = jax.random.PRNGKey(0)
    params = build_small_cnn(key)
    acc = AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)

    # 1 — plan once
    plan = plan_for_network(params, acc, batch=BATCH, cache=PlanCache())
    print(f"== plan: batch {BATCH}, flows "
          f"{[p.dataflow.value for p in plan.layers]}, tiles "
          f"{[(p.tile.block_m, p.tile.block_d) for p in plan.layers]} ==")

    # 2 — compile once (cold call traces + compiles)
    fn = compiled_forward(plan, cfg)
    x0 = jax.random.normal(jax.random.fold_in(key, 1),
                           (BATCH, 16, 16, 3))
    t0 = time.perf_counter()
    fn(params, x0, None)[0].block_until_ready()
    print(f"== cold call (trace + compile): "
          f"{time.perf_counter() - t0:.2f} s ==")

    # 3 — stream warm batches
    traces_before = trace_count()
    t0 = time.perf_counter()
    last = None
    for i in range(STREAM):
        x = jax.random.normal(jax.random.fold_in(key, 100 + i),
                              (BATCH, 16, 16, 3))
        last = execute_cnn(params, x, plan, cfg)  # compiled by default
    last.block_until_ready()
    dt = time.perf_counter() - t0
    ips = STREAM * BATCH / dt
    print(f"== streamed {STREAM} warm batches: {ips:,.0f} images/s "
          f"(host sim), retraces during stream: "
          f"{trace_count() - traces_before} ==")

    # eager baseline (the pre-fix behavior), one batch
    t0 = time.perf_counter()
    execute_cnn(params, x0, plan, cfg, compiled=False).block_until_ready()
    eager_s = time.perf_counter() - t0
    print(f"== eager baseline: {BATCH / eager_s:,.0f} images/s "
          f"-> compiled speedup {ips * eager_s / BATCH:,.0f}x ==")

    # 4 — traces materialize lazily, only now
    print("\n== per-layer trace of the last batch (lazy fingerprints) ==")
    for t in last.traces:
        print(f"   {t.name:6s} m={t.m:<6d} k={t.k:<4d} d={t.d:<4d} "
              f"{t.dataflow} tile=({t.block_m},{t.block_d}) "
              f"mean|out|={t.out_mean_abs:.4f}")
    print(f"\n   modeled (photonic perf model): {plan.fps:,.0f} FPS — "
          f"different machine, never compare to host img/s directly")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
