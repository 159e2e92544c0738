"""The paper's own workload: CNN inference ON the simulated HEANA.

Trains a small CNN on a synthetic 10-class task, then runs its inference
with every conv/fc GEMM executed by the photonic simulation at the 8-bit
design point — HEANA (BPCA analog carry) vs MAW (per-chunk ADC) vs ideal
int8 — and reports the Table-4-style accuracy drops, plus the perf model's
FPS/FPS-per-W for the same accelerators on the paper's four CNNs.

  PYTHONPATH=src python examples/heana_cnn_inference.py
"""
from benchmarks.table4_accuracy import evaluate, train_model
from repro.core.perf_model import AcceleratorConfig, cnn_inference, gmean
from repro.core.types import Dataflow
from repro.models.cnn import CNN_ZOO


def main():
    print("training reference CNN (exact numerics)...")
    params = train_model()
    accs = {m: evaluate(params, m) for m in ("exact", "int8", "heana",
                                             "maw")}
    print("\n== Table-4 proxy: top-1 under analog numerics ==")
    for m, a in accs.items():
        drop = 100 * (accs["exact"] - a)
        print(f"  {m:6s}: top-1 {a:.4f}   drop {drop:+.2f}%")

    print("\n== Fig-11 headline: HEANA-OS vs best baseline (gmean, 4 CNNs,"
          " 1 GS/s) ==")
    ratios_fps, ratios_w = {"amw": [], "maw": []}, {"amw": [], "maw": []}
    for name, fn in CNN_ZOO.items():
        layers = fn()
        h = cnn_inference(layers,
                          AcceleratorConfig.equal_area("heana", Dataflow.OS,
                                                       1.0))
        for base in ("amw", "maw"):
            bf = max(cnn_inference(layers, AcceleratorConfig.equal_area(
                base, f, 1.0)).fps for f in Dataflow)
            bw = max(cnn_inference(layers, AcceleratorConfig.equal_area(
                base, f, 1.0)).fps_per_watt for f in Dataflow)
            ratios_fps[base].append(h.fps / bf)
            ratios_w[base].append(h.fps_per_watt / bw)
    for base in ("amw", "maw"):
        print(f"  vs {base}: {gmean(ratios_fps[base]):6.1f}x FPS   "
              f"{gmean(ratios_w[base]):5.1f}x FPS/W   "
              f"(paper: >=66x / >=84x)")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
