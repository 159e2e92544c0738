"""The batched serving engine: buckets, warmup, micro-batching, stats.

Demonstrates exec.serving (ISSUE 4) end to end:

1. Build a ServingEngine for a zoo network: every power-of-two batch
   bucket gets its own auto-scheduled CnnPlan up front (shared plan
   cache), and ``warmup()`` pre-traces every executable — after it, no
   request ever pays a trace.
2. Serve mixed-size requests: each is padded to the smallest bucket that
   fits and sliced back (zero retraces, bitwise equal to an exact-size
   batch).
3. Coalesce single-image requests through the thread-safe MicroBatcher
   (each batch's logits are read to the host once; Futures resolve
   with each request's row as a numpy array).
4. If several devices are visible (e.g. XLA_FLAGS=
   --xla_force_host_platform_device_count=4), serve the same traffic
   data-parallel: the bucketed batch is sharded over the batch axis with
   a NamedSharding and the logits are bitwise equal to single-device.
5. Print the serving metrics: p50/p99 latency, sustained throughput,
   padding overhead, cache stats.

Run:  PYTHONPATH=src python examples/serving_engine.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perf_model import AcceleratorConfig
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import MicroBatcher, ServingEngine, trace_count
from repro.models.zoo_cnn import ZOO

NETWORK = "small_cnn"
MAX_BATCH = 8
REQUEST_SIZES = (1, 3, 5, 8, 2, 8, 4, 1)


def main():
    zoo = ZOO[NETWORK]
    key = jax.random.PRNGKey(0)
    params = zoo.init_params(key)
    acc = AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)

    # 1 — bucketed plans + AOT warmup
    engine = ServingEngine(params, acc, cfg, lowering=zoo.graph,
                           in_hw=zoo.in_hw, max_batch=MAX_BATCH)
    cold = engine.warmup()
    print(f"== {NETWORK}: buckets {engine.buckets}, warmup "
          f"{ {b: round(s, 2) for b, s in cold.items()} } s ==")

    # 2 — mixed-size traffic, zero retraces
    h, w = zoo.in_hw
    traces0 = trace_count()
    t0 = time.perf_counter()
    for i, n in enumerate(REQUEST_SIZES):
        x = jax.random.normal(jax.random.fold_in(key, 100 + i),
                              (n, h, w, zoo.in_ch))
        logits = engine.infer(x)
        assert logits.shape == (n, zoo.num_classes)
    dt = time.perf_counter() - t0
    n_imgs = sum(REQUEST_SIZES)
    print(f"== served {len(REQUEST_SIZES)} mixed-size requests "
          f"({n_imgs} images) in {dt:.2f} s — retraces: "
          f"{trace_count() - traces0} ==")

    # 3 — micro-batched single-image traffic
    with MicroBatcher(engine, max_delay_s=0.01) as mb:
        futs = [mb.submit(jax.random.normal(
            jax.random.fold_in(key, 200 + i), (h, w, zoo.in_ch)))
            for i in range(12)]
        outs = [f.result(timeout=60) for f in futs]
    assert all(isinstance(o, np.ndarray) and o.shape == (zoo.num_classes,)
               for o in outs)
    print(f"== micro-batcher coalesced 12 single-image requests: "
          f"{mb.stats()} ==")

    # 4 — data-parallel path (needs > 1 device)
    n_dev = len(jax.devices())
    if n_dev > 1 and MAX_BATCH % n_dev == 0:
        dp = ServingEngine(params, acc, cfg, lowering=zoo.graph,
                           in_hw=zoo.in_hw, max_batch=MAX_BATCH,
                           plan_cache=engine.plan_cache,
                           data_parallel=True)
        dp.warmup()
        x = jax.random.normal(jax.random.fold_in(key, 999),
                              (MAX_BATCH, h, w, zoo.in_ch))
        same = bool((jax.device_get(dp.infer(x)) ==
                     jax.device_get(engine.infer(x))).all())
        print(f"== data-parallel over {n_dev} devices: logits bitwise "
              f"equal to single-device = {same} ==")
    else:
        print(f"== data-parallel skipped ({n_dev} device(s); try "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=4) ==")

    # 5 — serving metrics
    s = engine.stats()
    print("\n== serving stats ==")
    print(f"   requests {s['requests']}, images {s['images']}, "
          f"batches {s['batches']}")
    print(f"   mean service time {s['latency_mean_s'] * 1e3:.1f} ms; "
          f"{s['infer_s_total'] * 1e3:.0f} ms inside infer in all, "
          f"{s['device_wait_s_total'] * 1e3:.0f} ms of it waiting for "
          f"the device; sustained {s['sustained_ips']:,.0f} img/s "
          f"(host sim)")
    print(f"   padding overhead {100 * s['padding_fraction']:.1f}% of "
          f"executed slots; retraces since warmup "
          f"{s['retraces_since_warmup']}")
    print(f"   plan cache {s['plan_cache']['hits']}h/"
          f"{s['plan_cache']['misses']}m; compiled wrappers "
          f"{s['compile_cache']['entries']}")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
