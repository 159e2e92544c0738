"""Auto-scheduled per-layer dataflows vs fixed dataflows (exec engine).

Three claims, measured:

  * planning: on every CNN in the zoo, at batch 1 and 256, the
    auto-schedule's perf-model FPS is >= the best single fixed dataflow
    (per-layer argmin can only tie or beat a global choice) — and on the
    thermo-optic baselines the mix is genuinely heterogeneous;
  * caching: re-planning the same shapes/config hits the
    content-addressed plan cache 100%;
  * execution: one end-to-end CNN inference through the Pallas TAOM
    kernel equals the pure-jnp reference bit-exactly with noise disabled.

Summaries are cached under experiments/autoflow/ for benchmarks/report.py.
"""
from __future__ import annotations

import os
from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import Row, timed
from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, execute_cnn, plan_for_network,
                        plan_summary, plan_vs_fixed, reference_forward,
                        schedule_cnn, save_summary)
from repro.models.cnn import CNN_ZOO, build_small_cnn

EXP_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "autoflow")
BACKENDS = ("heana", "amw", "maw")
BATCHES = (1, 256)


def _plan_rows(cache: PlanCache) -> List[Row]:
    rows: List[Row] = []
    all_ok = True
    for be in BACKENDS:
        for batch in BATCHES:
            for name, fn in CNN_ZOO.items():
                layers = fn()
                acc = pm.AcceleratorConfig.equal_area(be, Dataflow.OS, 1.0)
                plan, us = timed(schedule_cnn, layers, acc, batch,
                                 cache=cache)
                fixed = {f: pm.cnn_inference(
                    layers, pm.AcceleratorConfig.equal_area(be, f, 1.0),
                    batch).fps for f in Dataflow}
                cmp = plan_vs_fixed(plan, fixed)
                ok = plan.fps >= cmp["best_fixed_fps"] * (1 - 1e-12)
                all_ok &= ok
                summary = plan_summary(plan, name)
                summary["vs_fixed"] = cmp
                save_summary(summary, EXP_DIR, f"{be}_{name}_b{batch}.json")
                rows.append(Row(f"autoflow/{be}/{name}/b{batch}/uplift",
                                us, round(cmp["uplift"], 4)))
                mix = plan.mix()
                rows.append(Row(f"autoflow/{be}/{name}/b{batch}/mix_os_is_ws",
                                us, f"{mix['os']}-{mix['is']}-{mix['ws']}"))
    rows.append(Row("autoflow/auto_ge_best_fixed_all", 0.0, int(all_ok)))
    return rows


def _cache_rows(cache: PlanCache) -> List[Row]:
    """Re-plan the whole grid: every layer plan must be a cache hit."""
    hits = misses = 0
    for be in BACKENDS:
        for batch in BATCHES:
            for name, fn in CNN_ZOO.items():
                acc = pm.AcceleratorConfig.equal_area(be, Dataflow.OS, 1.0)
                plan = schedule_cnn(fn(), acc, batch, cache=cache)
                hits += plan.cache_hits
                misses += plan.cache_misses
    rate = hits / max(hits + misses, 1)
    return [Row("autoflow/cache/replan_hit_rate", 0.0, round(rate, 4)),
            Row("autoflow/cache/entries", 0.0, cache.stats()["entries"])]


def _exec_rows() -> List[Row]:
    """End-to-end small-CNN inference through the Pallas kernel."""
    key = jax.random.PRNGKey(0)
    params = build_small_cnn(key)
    batch = 4
    x = jax.random.normal(jax.random.fold_in(key, 1), (batch, 16, 16, 3))
    acc = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    # bits=6 keeps every integer partial sum < 2^24, so float summation
    # order cannot break the bit-exactness contract at any K here.
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)
    plan = plan_for_network(params, acc, batch=batch)
    res, us = timed(execute_cnn, params, x, plan, cfg, impl="pallas")
    ref = reference_forward(params, x, cfg)
    exact = bool(jnp.all(res.logits == ref))
    from repro.exec import execution_summary
    summary = execution_summary(res, "small_cnn", numerics={
        "bitexact_vs_ref": exact,
        "max_abs_diff": float(jnp.max(jnp.abs(res.logits - ref))),
        "batch": batch, "bits": cfg.bits})
    save_summary(summary, EXP_DIR, "exec_small_cnn.json")
    return [
        Row("autoflow/exec/small_cnn/bitexact_vs_ref", us, int(exact)),
        Row("autoflow/exec/small_cnn/us_per_image", us / batch,
            round(res.plan.fps, 1)),
    ]


def run() -> List[Row]:
    cache = PlanCache()
    rows = _plan_rows(cache)
    rows += _cache_rows(cache)
    rows += _exec_rows()
    return rows


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    for r in run():
        print(r.csv())
