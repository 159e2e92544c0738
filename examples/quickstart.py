"""Quickstart: the paper's technique in five snippets.

1. Scalability analysis (Fig. 9): how large can a HEANA DPU be?
2. A photonic matmul: HEANA vs AMW vs exact numerics.
3. The Pallas TAOM kernel vs its oracle.
4. System-level FPS/FPS-per-watt (Fig. 11) for ResNet50.
5. An LM forward pass running *through* the photonic backend.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import Backend, PhotonicConfig, max_dpe_size
from repro.core.perf_model import AcceleratorConfig, cnn_inference
from repro.core.photonic_gemm import design_point
from repro.core.types import Dataflow
from repro.kernels import ops
from repro.models.cnn import CNN_ZOO


def main():
    # 1 — scalability (paper Fig. 9): the hitless TAOM arrangement lets
    # HEANA run much wider optical dot products than AMW/MAW.
    print("== DPU size N at 4-bit, 1 GS/s ==")
    for be in ("heana", "amw", "maw"):
        print(f"  {be:6s} N = {max_dpe_size(be, 4, 1.0)}")

    # 2 — photonic numerics as a drop-in matmul
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 512))
    w = jax.random.normal(jax.random.fold_in(key, 1), (512, 64))
    exact = x @ w
    print("\n== photonic matmul rel-RMSE vs exact (4-bit design points) ==")
    for be in (Backend.HEANA, Backend.AMW):
        cfg = design_point(be, bits=4, data_rate_gsps=1.0)
        out = ops.photonic_matmul(x, w, cfg, key=jax.random.fold_in(key, 2))
        err = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        print(f"  {be.value:6s} N={cfg.dpe_size:3d}  rel-rmse={err:.4f}")

    # 3 — the Pallas kernel path agrees with the jnp oracle
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=8, dpe_size=128,
                         noise_enabled=False)
    a = ops.photonic_matmul(x, w, cfg, impl="pallas")
    b = ops.photonic_matmul(x, w, cfg, impl="ref")
    print(f"\n== pallas vs oracle max diff: "
          f"{float(jnp.max(jnp.abs(a - b))):.2e} ==")

    # 4 — system-level evaluation (paper Fig. 11, ResNet50 @ 1 GS/s)
    print("\n== ResNet50 FPS / FPS-per-W (equal-area, 1 GS/s) ==")
    layers = CNN_ZOO["resnet50"]()
    for be, flow in (("heana", Dataflow.OS), ("amw", Dataflow.WS),
                     ("maw", Dataflow.WS)):
        r = cnn_inference(layers, AcceleratorConfig.equal_area(be, flow, 1.0))
        print(f"  {be:6s}-{flow.value}: {r.fps:12.0f} FPS   "
              f"{r.fps_per_watt:8.2f} FPS/W")

    # 5 — an LM forward through the photonic backend
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.models.layers import PhotonicCtx
    cfg_lm = get_config("qwen2-0.5b", smoke=True)
    params = zoo.init_params(cfg_lm, key)
    tokens = jax.random.randint(key, (2, 32), 0, cfg_lm.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    for name, ctx in (("exact", PhotonicCtx()),
                      ("heana-8bit", PhotonicCtx(cfg=PhotonicConfig(
                          backend=Backend.HEANA, bits=8, adc_bits=12,
                          dpe_size=128, noise_enabled=False), impl="ref"))):
        loss = zoo.loss_fn(params, batch, cfg_lm, ctx=ctx)
        print(f"  qwen2-0.5b(smoke) loss under {name:10s}: {float(loss):.4f}")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
