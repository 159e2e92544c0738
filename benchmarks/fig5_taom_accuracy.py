"""Paper Fig. 5: TAOM accuracy/precision vs optical power and sample rate.

The paper measured these surfaces with Lumerical transient simulations; we
reproduce them from the closed-form noise model (DESIGN.md §6.1): accuracy
is log2(1/MAE) of simulated dot products against ideal, exactly the
paper's metric, evaluated on the analytic TAOM+BPCA simulation.

Expected qualitative trends (asserted by tests/test_benchmarks.py):
  * accuracy rises with optical power,
  * accuracy falls with sample rate (higher DR -> more noise bandwidth),
  * precision (resolvable bits) rises with the time-step size.
"""
from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import Row, timed
from repro.core import noise as noise_mod
from repro.core.photonic_gemm import photonic_dot_general
from repro.core.types import Backend, OpticalParams, PhotonicConfig


def accuracy_bits(power_dbm: float, dr_gsps: float, bits: int = 8,
                  n: int = 32, trials: int = 8) -> float:
    """log2(1/MAE), MAE normalized to the dot-product full scale."""
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=bits, adc_bits=10,
                         dpe_size=n, data_rate_gsps=dr_gsps,
                         pd_power_dbm=power_dbm)
    key = jax.random.PRNGKey(0)
    maes = []
    for t in range(trials):
        kx, kw, kn = jax.random.split(jax.random.fold_in(key, t), 3)
        x = jax.random.uniform(kx, (8, n), minval=-1, maxval=1)
        w = jax.random.uniform(kw, (n, 8), minval=-1, maxval=1)
        ideal = x @ w
        got = photonic_dot_general(x, w, cfg, key=kn)
        fs = float(jnp.max(jnp.abs(ideal))) + 1e-9
        maes.append(float(jnp.mean(jnp.abs(got - ideal))) / fs)
    mae = max(sum(maes) / len(maes), 1e-9)
    return math.log2(1.0 / mae)


def run() -> List[Row]:
    rows: List[Row] = []
    # 8-bit operands: the receiver is noise-limited (not quantization-
    # limited) across this power range, so the paper's trends are visible.
    powers = (-20.0, -10.0, 0.0, 10.0)
    rates = (1.0, 5.0, 10.0)
    for p in powers:
        for dr in rates:
            acc, us = timed(accuracy_bits, p, dr)
            rows.append(Row(f"fig5/accuracy_bits/p{int(p)}dbm/dr{int(dr)}",
                            us, round(acc, 2)))
    # precision = ENOB from the receiver model (paper's Eq. 1 view)
    o = OpticalParams()
    for p in powers:
        for dr in rates:
            enob, us = timed(noise_mod.enob, p, dr, o)
            rows.append(Row(f"fig5/precision_enob/p{int(p)}dbm/dr{int(dr)}",
                            us, round(enob, 2)))
    return rows


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    for r in run():
        print(r.csv())
