"""The execution engine end-to-end: plan, execute, report.

1. Auto-schedule per-layer dataflows for the paper's CNNs — on HEANA the
   plan keeps OS (or a free-latency WS swap on tiny layers); on the
   thermo-optic AMW baseline it mixes WS with IS for the fc layer.
2. Show the content-addressed plan cache: re-planning is all hits.
3. Execute a small CNN end-to-end through the Pallas TAOM kernel and
   check it against the pure-jnp reference bit-exactly (noise off), then
   run it with detection noise threaded per layer.

Run:  PYTHONPATH=src python examples/autoflow_inference.py
"""
import jax
import jax.numpy as jnp

from repro.core.perf_model import AcceleratorConfig, cnn_inference
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, execute_cnn, plan_for_network, plan_table,
                        reference_forward, schedule_cnn)
from repro.models.cnn import CNN_ZOO, build_small_cnn


def main():
    # 1 — per-layer dataflow auto-scheduling
    cache = PlanCache()
    print("== auto-scheduled dataflow mix (batch 1, 1 GS/s) ==")
    for be in ("heana", "amw"):
        acc = AcceleratorConfig.equal_area(be, Dataflow.OS, 1.0)
        for name, fn in CNN_ZOO.items():
            layers = fn()
            plan = schedule_cnn(layers, acc, batch=1, cache=cache)
            best_fixed = max(cnn_inference(
                layers, AcceleratorConfig.equal_area(be, f, 1.0)).fps
                for f in Dataflow)
            mix = plan.mix()
            print(f"  {be:6s} {name:14s} mix os/is/ws = "
                  f"{mix['os']}/{mix['is']}/{mix['ws']}   "
                  f"auto {plan.fps:12.1f} FPS  (best fixed "
                  f"{best_fixed:12.1f}, x{plan.fps / best_fixed:.3f})")

    # 2 — the plan cache makes re-planning free
    plan = schedule_cnn(CNN_ZOO["googlenet"](),
                        AcceleratorConfig.equal_area("heana", Dataflow.OS,
                                                     1.0),
                        batch=1, cache=cache)
    print(f"\n== re-plan googlenet: {plan.cache_hits} hits / "
          f"{plan.cache_misses} misses ({len(cache)} cached plans) ==")
    print("\n== googlenet plan, heaviest layers ==")
    print(plan_table(plan, max_rows=5))

    # 3 — end-to-end execution through the Pallas kernel
    key = jax.random.PRNGKey(0)
    params = build_small_cnn(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 16, 3))
    acc = AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    exec_plan = plan_for_network(params, acc, batch=4, cache=cache)

    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)
    res = execute_cnn(params, x, exec_plan, cfg, impl="pallas")
    ref = reference_forward(params, x, cfg)
    print(f"\n== executed small CNN (Pallas) vs jnp reference: bit-exact = "
          f"{bool(jnp.all(res.logits == ref))} ==")
    print(f"   modeled: {exec_plan.fps:.0f} FPS, "
          f"{exec_plan.latency_s * 1e9:.2f} ns/batch; per-layer flows: "
          f"{[t.dataflow for t in res.traces]}")

    cfg_noisy = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                               noise_enabled=True)
    noisy = execute_cnn(params, x, exec_plan, cfg_noisy,
                        key=jax.random.PRNGKey(7), impl="pallas")
    drift = float(jnp.linalg.norm(noisy.logits - res.logits) /
                  jnp.linalg.norm(res.logits))
    print(f"   with detection noise (per-layer keys): rel logit drift "
          f"{drift:.4f}")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
