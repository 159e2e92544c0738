"""Report layer: plan + execution summaries for humans and for the
benchmark harness.

Aggregates the scheduler's per-layer modeled latency/energy next to the
executor's actual numerics, renders markdown (examples) and emits
JSON-safe dicts (benchmarks/autoflow.py caches them under
experiments/autoflow/ for benchmarks/report.py to assemble).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

from repro.core.types import Dataflow
from repro.exec.executor import ExecutionResult
from repro.exec.scheduler import CnnPlan
from repro.models.lowering import OpGraph


def graph_summary(graph: OpGraph, name: str = "") -> dict:
    """JSON-safe structural summary of a lowered op graph (the zoo's
    networks): op histogram + GEMM-layer count, for reports/examples."""
    ops: Dict[str, int] = {}
    for n in graph.nodes:
        ops[n.op] = ops.get(n.op, 0) + 1
    return {
        "name": name,
        "n_nodes": len(graph.nodes),
        "n_gemm_layers": len(graph.gemm_nodes),
        "ops": ops,
        "output": graph.output.name,
    }


def plan_summary(plan: CnnPlan, name: str = "") -> dict:
    """JSON-safe summary of an auto-scheduled plan."""
    top = sorted(plan.layers, key=lambda p: -p.latency_s)[:5]
    return {
        "name": name,
        "backend": plan.acc.backend,
        "data_rate_gsps": plan.acc.data_rate_gsps,
        "batch": plan.batch,
        "objective": plan.objective,
        "n_layers": len(plan.layers),
        "dataflow_mix": plan.mix(),
        "fps": plan.fps,
        "fps_per_watt": plan.fps_per_watt,
        "latency_s": plan.latency_s,
        "energy_j": plan.result.energy_j,
        "cache_hits": plan.cache_hits,
        "cache_misses": plan.cache_misses,
        "top_layers": [
            {"name": p.name, "shape": [p.c, p.k, p.d],
             "dataflow": p.dataflow.value, "latency_s": p.latency_s,
             "share": p.latency_s / max(plan.latency_s, 1e-30)}
            for p in top],
    }


def plan_table(plan: CnnPlan, max_rows: int = 0) -> str:
    """Markdown per-layer table of an auto-scheduled plan."""
    rows = plan.layers[:max_rows] if max_rows else plan.layers
    total = max(plan.latency_s, 1e-30)
    lines = [
        "| layer | C | K | D | flow | tile (m,d) | latency | share |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for p in rows:
        lines.append(
            f"| {p.name} | {p.c} | {p.k} | {p.d} | {p.dataflow.value} | "
            f"{p.tile.block_m},{p.tile.block_d} | {p.latency_s:.3e} s | "
            f"{100 * p.latency_s / total:.1f}% |")
    if max_rows and len(plan.layers) > max_rows:
        lines.append(f"| ... {len(plan.layers) - max_rows} more | | | | | "
                     f"| | |")
    return "\n".join(lines)


def plan_vs_fixed(plan: CnnPlan, fixed: Dict[Dataflow, float]) -> dict:
    """Compare a plan's FPS against fixed-dataflow FPS numbers."""
    best_flow, best_fps = max(fixed.items(), key=lambda kv: kv[1])
    return {
        "auto_fps": plan.fps,
        "fixed_fps": {f.value: v for f, v in fixed.items()},
        "best_fixed_flow": best_flow.value,
        "best_fixed_fps": best_fps,
        "uplift": plan.fps / best_fps if best_fps > 0 else float("inf"),
    }


def execution_summary(res: ExecutionResult, name: str = "",
                      numerics: Optional[dict] = None) -> dict:
    """Modeled plan totals next to executed-numerics evidence."""
    energy = res.energy()
    out = {
        "name": name,
        "batch": res.plan.batch,
        "modeled_fps": res.plan.fps,
        "modeled_latency_s": res.plan.latency_s,
        "dataflow_mix": res.plan.mix(),
        "executed_energy_j": energy.energy_j,
        "executed_j_per_image": energy.j_per_image,
        "executed_fps_per_watt": energy.fps_per_watt,
        "energy_breakdown": {
            f: getattr(energy.breakdown, f)
            for f in ("laser", "dac", "adc", "tuning", "buffer",
                      "reduction", "static")},
        "layers": [
            {"name": t.name, "m": t.m, "k": t.k, "d": t.d,
             "dataflow": t.dataflow, "tile": [t.block_m, t.block_d],
             "latency_s": t.latency_s, "energy_j": t.energy_j,
             "executed_energy_j": t.executed_energy_j,
             "n_chunks": t.n_chunks,
             "adc_conversions": t.adc_conversions,
             "out_mean_abs": t.out_mean_abs}
            for t in res.traces],
    }
    if res.plan.op is not None:
        out["operating_point"] = res.plan.op.describe()
    if numerics:
        out["numerics"] = dict(numerics)
    return out


def energy_summary(name: str, op, executed, analytic,
                   extras: Optional[dict] = None) -> dict:
    """JSON-safe record of one executed-trace energy measurement.

    ``op`` is the OperatingPoint everything was derived from, ``executed``
    a core.hw.TraceEnergy from the executed plan, ``analytic`` the
    perf_model.InferenceResult predicted for the same network/hardware —
    the coherence evidence (their relative gap) rides along explicitly.
    """
    def rel(a, b):
        return abs(a - b) / max(abs(b), 1e-30)

    return {
        "kind": "energy",
        "name": name,
        "operating_point": op.describe(),
        "batch": executed.batch,
        "executed_fps": executed.fps,
        "executed_fps_per_watt": executed.fps_per_watt,
        "executed_energy_j": executed.energy_j,
        "executed_j_per_image": executed.j_per_image,
        "executed_watts": executed.watts,
        "analytic_fps": analytic.fps,
        "analytic_fps_per_watt": analytic.fps_per_watt,
        "analytic_energy_j": analytic.energy_j,
        "fps_rel_gap": rel(executed.fps, analytic.fps),
        "fpsw_rel_gap": rel(executed.fps_per_watt, analytic.fps_per_watt),
        **({} if not extras else dict(extras)),
    }


def save_summary(summary: dict, directory: str, filename: str) -> str:
    """Write a summary JSON under ``directory`` (created if missing)."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, filename)
    with open(path, "w") as fh:
        json.dump(summary, fh, indent=1, sort_keys=True)
    return path
