"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract).  The roofline
rows are read from cached ``experiments/roofline`` JSONs (generated by
``python -m benchmarks.roofline``, which needs a fresh process with 512
host devices); missing cells are reported as such rather than silently
dropped.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import traceback


def _roofline_rows():
    from benchmarks.common import Row
    rows = []
    root = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "roofline")
    files = sorted(glob.glob(os.path.join(root, "*.json")))
    if not files:
        return [Row("roofline/missing_run_benchmarks.roofline", 0.0, 0)]
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        cell = f"{rec['arch']}/{rec['shape']}"
        if rec["status"] != "ok":
            rows.append(Row(f"roofline/{cell}/status", 0.0, rec["status"]))
            continue
        rows.append(Row(f"roofline/{cell}/dominant",
                        rec.get("wall_s", 0) * 1e6, rec["dominant"]))
        rows.append(Row(f"roofline/{cell}/compute_s", 0.0,
                        f"{rec['compute_s']:.3e}"))
        rows.append(Row(f"roofline/{cell}/memory_s", 0.0,
                        f"{rec['memory_s']:.3e}"))
        rows.append(Row(f"roofline/{cell}/collective_s", 0.0,
                        f"{rec['collective_s']:.3e}"))
        rows.append(Row(f"roofline/{cell}/model_hlo_ratio", 0.0,
                        f"{rec['model_hlo_ratio']:.3f}"))
    return rows


MODULES = [
    ("fig9_scalability", "benchmarks.fig9_scalability"),
    ("fig1_buffer_accesses", "benchmarks.fig1_buffer_accesses"),
    ("fig5_taom_accuracy", "benchmarks.fig5_taom_accuracy"),
    ("fig11_12_fps", "benchmarks.fig11_fps"),
    ("fig13_14_bpca", "benchmarks.fig13_bpca_baselines"),
    ("table4_accuracy", "benchmarks.table4_accuracy"),
    ("autoflow", "benchmarks.autoflow"),
    ("energy", "benchmarks.energy"),
]


def main() -> None:
    import importlib
    print("name,us_per_call,derived")
    failures = 0
    for tag, modname in MODULES:
        try:
            mod = importlib.import_module(modname)
            for row in mod.run():
                print(row.csv(), flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{tag}/ERROR,0,failed", flush=True)
            traceback.print_exc(file=sys.stderr)
    for row in _roofline_rows():
        print(row.csv(), flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
