"""Beyond-paper feature: photonic-aware QAT.

Trains the same tiny LM twice — exact numerics vs *through* the HEANA
simulation (STE gradients, detection noise on) — then evaluates both under
HEANA inference numerics.

Honest finding (EXPERIMENTS.md §Numerics extras): at smoke scale this is a
NULL RESULT — straight-through gradients make the two runs near-identical,
so the script demonstrates the *mechanism* (trainability through the
photonic simulation for every arch family), not a measured QAT win.

  PYTHONPATH=src python examples/photonic_qat.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.photonic_gemm import design_point
from repro.core.types import Backend
from repro.data.pipeline import DataConfig, make_source
from repro.models import model_zoo as zoo
from repro.models.layers import PhotonicCtx
from repro.optim import optimizer as opt

STEPS, BATCH, SEQ = 200, 8, 64


def run(train_ctx: PhotonicCtx, eval_ctx: PhotonicCtx, seed=0):
    cfg = get_config("qwen2-0.5b", smoke=True)
    adam = opt.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=STEPS)
    params = zoo.init_params(cfg, jax.random.PRNGKey(seed))
    state = opt.init(params)
    data = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                  global_batch=BATCH, seed=seed))

    @jax.jit
    def step(params, state, tokens, targets, key):
        ctx = PhotonicCtx(cfg=train_ctx.cfg, key=key, impl="ref") \
            if train_ctx.cfg else train_ctx

        def loss_fn(p):
            return zoo.loss_fn(p, {"tokens": tokens, "targets": targets},
                               cfg, ctx=ctx)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state, _ = opt.apply(adam, params, state, grads)
        return params, state, loss

    for s in range(STEPS):
        b = data.batch(s)
        params, state, loss = step(params, state, jnp.asarray(b["tokens"]),
                                   jnp.asarray(b["targets"]),
                                   jax.random.PRNGKey(1000 + s))
    # eval under photonic inference numerics
    eval_losses = []
    for s in range(5):
        b = data.batch(10_000 + s)
        eval_losses.append(float(zoo.loss_fn(
            params, {"tokens": jnp.asarray(b["tokens"]),
                     "targets": jnp.asarray(b["targets"])}, cfg,
            ctx=eval_ctx)))
    return float(loss), sum(eval_losses) / len(eval_losses)


def main():
    heana = design_point(Backend.HEANA, bits=4, data_rate_gsps=1.0,
                         adc_bits=8)
    eval_ctx = PhotonicCtx(cfg=heana, key=jax.random.PRNGKey(9), impl="ref")
    print("training EXACT, evaluating on HEANA numerics...")
    tr_loss_e, ev_e = run(PhotonicCtx(), eval_ctx)
    print(f"  train loss {tr_loss_e:.4f} -> HEANA eval loss {ev_e:.4f}")
    print("training THROUGH HEANA (QAT), evaluating on HEANA numerics...")
    tr_loss_q, ev_q = run(PhotonicCtx(cfg=heana, impl="ref"), eval_ctx)
    print(f"  train loss {tr_loss_q:.4f} -> HEANA eval loss {ev_q:.4f}")
    gap = ev_e - ev_q
    print(f"\nQAT advantage on photonic hardware: {gap:+.4f} nats "
          f"({'QAT better' if gap > 0 else 'exact better'})")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
