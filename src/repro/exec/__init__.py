"""Photonic execution engine: dataflow auto-scheduler + Pallas CNN executor.

The three layers (see ISSUE 1 / paper §4, §6.3):

  * scheduler — per-layer {OS, IS, WS} x tiling search over the
    event-driven perf model, with a content-addressed plan cache;
  * executor  — runs each planned GEMM through the Pallas TAOM kernel
    (quantize -> kernel -> rescale), batch folded into the GEMM M axis,
    noise keys threaded per layer; the serving hot path is a jit-compiled
    pure forward (compiled_forward / forward_fn) with the plan's tilings
    baked in as static arguments and zero per-layer host syncs;
  * report    — modeled latency/energy aggregated next to executed
    numerics, feeding benchmarks/autoflow.py and examples;

Hardware is specified ONCE: pass a core.hw.OperatingPoint wherever an
AcceleratorConfig is accepted — the plan embeds it (plan v4), the
executor holds the kernel PhotonicConfig coherent with it, and
executed-trace energy (ExecutionResult.energy(), LayerTrace.
executed_energy_j, ServingEngine.stats() joules/watts) is charged from
it via the same perf-model event accounting as the analytic figures
(ISSUE 5).
  * serving   — batched multi-device serving engine over the compiled
    forward: power-of-two batch buckets (one pre-traced plan each, AOT
    warmup), a thread-safe micro-batcher coalescing single-image
    requests, a data-parallel path sharding the bucketed batch over
    jax.devices() (bitwise equal to single-device, noise off), and
    p50/p99/throughput/padding metrics (ISSUE 4).

Networks are described by the lowering IR (models.lowering.OpGraph —
stride/padding convs, depthwise convs, pooling, residual adds, concats,
channel shuffles); models.zoo_cnn registers reduced-scale runnable
variants of the paper's four evaluation CNNs on it (ISSUE 3).
"""
from repro.exec.executor import (ExecutionResult, LayerTrace,
                                 compile_cache_stats, compiled_forward,
                                 execute_cnn, forward_fn,
                                 lowering_fingerprint, plan_for_network,
                                 reference_forward, trace_count)
from repro.exec.plan_cache import GLOBAL_PLAN_CACHE, PlanCache, fingerprint
from repro.exec.report import (energy_summary, execution_summary,
                               graph_summary, plan_summary, plan_table,
                               plan_vs_fixed, save_summary)
from repro.exec.scheduler import (CnnPlan, FrozenCandidates, LayerPlan,
                                  TileChoice, plan_layer, schedule_buckets,
                                  schedule_cnn)
from repro.exec.serving import (MicroBatcher, ServingEngine, bucket_for,
                                power_of_two_buckets)

__all__ = [
    "CnnPlan", "FrozenCandidates", "LayerPlan", "TileChoice", "plan_layer",
    "schedule_cnn", "schedule_buckets",
    "ServingEngine", "MicroBatcher", "power_of_two_buckets", "bucket_for",
    "PlanCache", "GLOBAL_PLAN_CACHE", "fingerprint",
    "ExecutionResult", "LayerTrace", "execute_cnn", "plan_for_network",
    "reference_forward", "compiled_forward", "forward_fn", "trace_count",
    "compile_cache_stats", "lowering_fingerprint",
    "plan_summary", "plan_table", "plan_vs_fixed", "execution_summary",
    "graph_summary", "save_summary", "energy_summary",
]
