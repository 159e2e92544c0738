"""Executable model zoo demo: the paper's four evaluation CNNs as
reduced-scale runnable graphs, planned and executed end-to-end.

For each network: build params from the graph, auto-schedule dataflows
and tilings, run the compiled Pallas path, and verify the output is
bit-exact against the pure-jnp oracle under the same compilation (the
compiled forward with the reference GEMM) with zero warm-call retraces.

``--smoke`` (the CI zoo-smoke gate) runs one ResNet + one MobileNet
variant and exits non-zero on any conformance violation — the graph
execution path cannot silently rot.

Run:  PYTHONPATH=src python examples/zoo_inference.py [--smoke]
"""
import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, execute_cnn, graph_summary,
                        plan_for_network, plan_table, trace_count)
from repro.models.zoo_cnn import PAPER_ZOO

HEANA = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)


def run_model(model, batch=2, seed=0, verbose=True) -> bool:
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (batch, *model.in_hw, model.in_ch))
    plan = plan_for_network(params, HEANA, batch=batch, in_hw=model.in_hw,
                            lowering=model.graph, cache=PlanCache())
    res = execute_cnn(params, x, plan, cfg, impl="pallas",
                      lowering=model.graph).block_until_ready()
    ref = execute_cnn(params, x, plan, cfg, impl="ref",
                      lowering=model.graph)
    exact = bool(jnp.all(res.logits == ref.logits))
    before = trace_count()
    execute_cnn(params, x, plan, cfg, impl="pallas", lowering=model.graph)
    no_retrace = trace_count() == before

    s = graph_summary(model.graph, model.name)
    if verbose:
        print(f"\n## {model.name}  ({s['n_nodes']} nodes, "
              f"{s['n_gemm_layers']} GEMM layers, ops={s['ops']})")
        print(f"   modeled fps={plan.fps:.1f}  mix={plan.mix()}  "
              f"logits={tuple(res.logits.shape)}")
        print(f"   bit-exact vs oracle: {exact}   "
              f"zero warm retraces: {no_retrace}")
        print(plan_table(plan, max_rows=6))
    if not exact:
        print(f"FAIL {model.name}: compiled output != oracle",
              file=sys.stderr)
    if not no_retrace:
        print(f"FAIL {model.name}: warm call retraced", file=sys.stderr)
    return exact and no_retrace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: one ResNet + one MobileNet only")
    args = ap.parse_args()
    names = (["resnet_mini", "mobilenet_mini"] if args.smoke
             else list(PAPER_ZOO))
    ok = all([run_model(PAPER_ZOO[n], verbose=not args.smoke)
              for n in names])
    if not ok:
        sys.exit(1)
    print(f"\nzoo {'smoke ' if args.smoke else ''}conformance: "
          f"{len(names)}/{len(names)} networks bit-exact, no retraces")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
