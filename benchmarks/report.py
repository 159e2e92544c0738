"""Assemble EXPERIMENTS.md §Dry-run and §Roofline tables from the cached
experiment JSONs.  Pure file-reading — safe to run anywhere:

  PYTHONPATH=src python -m benchmarks.report            # prints markdown
"""
from __future__ import annotations

import glob
import json
import os

ROOT = os.path.join(os.path.dirname(__file__), "..", "experiments")


def _load(sub):
    recs = []
    for f in sorted(glob.glob(os.path.join(ROOT, sub, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def _fmt(x, unit=""):
    if x is None:
        return "-"
    if isinstance(x, float):
        if x == 0:
            return "0"
        if abs(x) >= 1e13 or abs(x) < 1e-3:
            return f"{x:.2e}{unit}"
        return f"{x:,.0f}{unit}" if abs(x) >= 100 else f"{x:.3g}{unit}"
    return f"{x}{unit}"


def dryrun_table() -> str:
    recs = _load("dryrun")
    lines = [
        "| arch | shape | mesh | status | compile s | HLO GFLOP/dev | "
        "coll MB/dev | arg MB/dev | temp MB/dev |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["status"] == "ok":
            mem = r.get("memory", {})
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | "
                f"{r.get('compile_s', '-')} | "
                f"{_fmt((r.get('flops') or 0) / 1e9)} | "
                f"{_fmt(r['collectives']['total_bytes'] / 1e6)} | "
                f"{_fmt((mem.get('argument_size_in_bytes') or 0) / 1e6)} | "
                f"{_fmt((mem.get('temp_size_in_bytes') or 0) / 1e6)} |")
        else:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                f"{r['status']} | - | - | - | - | - |")
    n_ok = sum(1 for r in recs if r["status"] == "ok")
    n_skip = sum(1 for r in recs if r["status"] == "skipped")
    lines.append("")
    lines.append(f"**{n_ok} cells compiled, {n_skip} documented skips, "
                 f"{len(recs) - n_ok - n_skip} errors.**")
    return "\n".join(lines)


HINTS = {
    "compute": "raise arithmetic efficiency: fewer recompute passes "
               "(remat policy), fuse quant/dequant into the matmul",
    "memory": "cut bytes: keep logits vocab-sharded through the CE, "
              "bf16 intermediates, smaller attention working set",
    "collective": "cut collective volume: reduce-scatter instead of "
                  "all-reduce, overlap grad reduction with backward, "
                  "int8-compress the cross-pod hop",
}


def roofline_table() -> str:
    recs = _load("roofline")
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "MODEL_FLOPS | model/HLO | next move |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | "
                         f"{r['status']}: {r.get('reason', '')[:60]} | - | "
                         f"- | - |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['dominant']}** | {_fmt(r['model_flops'])} | "
            f"{r['model_hlo_ratio']:.2f} | {HINTS[r['dominant']]} |")
    return "\n".join(lines)


def autoflow_table() -> str:
    """Auto-scheduled vs fixed dataflows (exec engine), from cached
    experiments/autoflow JSONs (generated by benchmarks/autoflow.py)."""
    recs = [r for r in _load("autoflow") if "fps" in r]
    if not recs:
        return "*(no cached runs — `PYTHONPATH=src python -m " \
               "benchmarks.autoflow` to generate)*"
    lines = [
        "| cnn | backend | batch | auto FPS | best fixed (flow) | uplift | "
        "mix os/is/ws | cache h/m |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(recs, key=lambda r: (r["backend"], r["name"],
                                         r["batch"])):
        vs = r.get("vs_fixed", {})
        mix = r["dataflow_mix"]
        lines.append(
            f"| {r['name']} | {r['backend']} | {r['batch']} | "
            f"{_fmt(r['fps'])} | {_fmt(vs.get('best_fixed_fps'))} "
            f"({vs.get('best_fixed_flow', '-')}) | "
            f"{vs.get('uplift', 0):.3f}x | "
            f"{mix.get('os', 0)}/{mix.get('is', 0)}/{mix.get('ws', 0)} | "
            f"{r['cache_hits']}/{r['cache_misses']} |")
    ex = [r for r in _load("autoflow") if r.get("name") == "small_cnn"
          and "numerics" in r]
    if ex:
        n = ex[0]["numerics"]
        lines.append("")
        lines.append(f"**Executed small CNN through the Pallas kernel: "
                     f"bit-exact vs jnp reference = "
                     f"{n['bitexact_vs_ref']} (max |diff| = "
                     f"{n['max_abs_diff']:.1e}, noise off, "
                     f"{n['bits']}-bit).**")
    return "\n".join(lines)


def energy_table() -> str:
    """Executed-trace energy & FPS/W from one operating point, from
    cached experiments/energy JSONs (benchmarks/energy.py)."""
    recs = [r for r in _load("energy") if r.get("kind") == "energy"]
    if not recs:
        return "*(no cached runs — `PYTHONPATH=src python -m " \
               "benchmarks.energy` to generate)*"
    lines = [
        "| cnn | operating point (B, DR, N, DPUs) | batch | exec FPS | "
        "exec FPS/W | uJ/img | vs analytic (rel gap) |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in sorted(recs, key=lambda r: (r["name"], r["batch"])):
        op = r["operating_point"]
        gap = max(r.get("fps_rel_gap", 0), r.get("fpsw_rel_gap", 0))
        lines.append(
            f"| {r['name']} | {op['backend']}-{op['dataflow']} "
            f"B={op['bits']} DR={op['data_rate_gsps']:g} N={op['dpe_size']} "
            f"x{op['n_dpus']} | {r['batch']} | "
            f"{_fmt(r['executed_fps'])} | "
            f"{_fmt(r['executed_fps_per_watt'])} | "
            f"{_fmt(r['executed_j_per_image'] * 1e6)} | {gap:.1e} |")
    lines.append("")
    lines.append("*FPS and FPS/W are the photonic model's executed-trace "
                 "numbers (every config derived from one "
                 "core.hw.OperatingPoint); the last column is the "
                 "coherence gap vs the analytic cnn_inference prediction "
                 "— held to 1e-9 by benchmarks/energy.py.*")
    return "\n".join(lines)


def main() -> None:
    print("## §Dry-run (auto-generated by benchmarks/report.py)\n")
    print(dryrun_table())
    print("\n## §Roofline (single-pod 16x16, probe-corrected)\n")
    print(roofline_table())
    print("\n## §Autoflow (per-layer dataflow auto-scheduler)\n")
    print(autoflow_table())
    print("\n## §Energy (executed-trace accounting from one operating "
          "point)\n")
    print(energy_table())


if __name__ == "__main__":
    main()
