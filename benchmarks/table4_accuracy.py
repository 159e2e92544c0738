"""Paper Table 4: inference accuracy under analog photonic numerics.

Offline proxy for the ImageNet experiment (DESIGN.md §6.2): a small CNN is
trained (exact numerics, f32) on a synthetic 10-class image task, then
evaluated with its conv/fc GEMMs executed as:

    exact | int8 quantized | HEANA (8-bit, analog carry + noise) |
    MAW (8-bit, per-chunk ADC + noise)

Derived: top-1 accuracy and the drop vs exact — the paper's claim is a
<=0.1% drop for HEANA at 8-bit; our proxy shows the same near-zero drop
ordering (HEANA drop <= MAW drop).
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timed
from repro.core.photonic_gemm import design_point
from repro.core.types import Backend, PhotonicConfig
from repro.kernels import ops as kops
from repro.models.cnn import build_small_cnn, small_cnn_apply

HW, NCLASS = 16, 10


_TEMPLATES = jax.random.normal(jax.random.PRNGKey(42), (NCLASS, HW, HW, 3))


def make_data(n: int, key, noise=2.5):
    """FIXED class templates + Gaussian noise: a learnable 10-way task."""
    nkey, lkey = jax.random.split(key)
    labels = jax.random.randint(lkey, (n,), 0, NCLASS)
    x = _TEMPLATES[labels] + noise * jax.random.normal(nkey, (n, HW, HW, 3))
    return x, labels


def train_model(steps=150, lr=0.05, batch=64, seed=0):
    key = jax.random.PRNGKey(seed)
    params = build_small_cnn(jax.random.fold_in(key, 1), NCLASS, HW)

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            logits = small_cnn_apply(p, x)
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), y[:, None], axis=1))
        loss, g = jax.value_and_grad(loss_fn)(params)
        return jax.tree.map(lambda p, gi: p - lr * gi, params, g), loss

    for s in range(steps):
        x, y = make_data(batch, jax.random.fold_in(key, 1000 + s))
        params, loss = step(params, x, y)
    return params


def evaluate(params, numerics: str, n=512, seed=123) -> float:
    x, y = make_data(n, jax.random.PRNGKey(seed))
    if numerics == "exact":
        mm = None
    else:
        if numerics == "int8":
            cfg = PhotonicConfig(backend=Backend.INT_QUANT, bits=8,
                                 noise_enabled=False)
        elif numerics == "heana":
            cfg = design_point(Backend.HEANA, 8, 1.0, adc_bits=12)
        else:
            cfg = design_point(Backend.MAW, 8, 1.0, adc_bits=12)
        mm = functools.partial(kops.photonic_matmul, cfg=cfg,
                               key=jax.random.PRNGKey(7), impl="ref")
        mm = lambda a, w, _f=mm: _f(a, w)  # noqa: E731
    logits = small_cnn_apply(params, x, matmul=mm)
    return float(jnp.mean(jnp.argmax(logits, -1) == y))


def run() -> List[Row]:
    rows: List[Row] = []
    params, us_train = timed(train_model)
    accs = {}
    for mode in ("exact", "int8", "heana", "maw"):
        acc, us = timed(evaluate, params, mode)
        accs[mode] = acc
        rows.append(Row(f"table4/top1/{mode}", us, round(acc, 4)))
    for mode in ("int8", "heana", "maw"):
        rows.append(Row(f"table4/top1_drop_pct/{mode}", us_train,
                        round(100 * (accs["exact"] - accs[mode]), 2)))
    return rows


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    for r in run():
        print(r.csv())
