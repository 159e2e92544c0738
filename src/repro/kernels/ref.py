"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are swept against in
tests/test_kernels.py (interpret mode on CPU).  The taom_gemm oracle shares
its math with core.photonic_gemm but takes the *same explicit inputs* as the
kernel (pre-quantized operands, pre-sampled noise, calibrated ADC scale) so
comparisons are apples-to-apples.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core.photonic_gemm import detection_sigma
from repro.core.types import Backend, PhotonicConfig
# The ADC model is shared with the kernel, python-float full scale and
# all: both sides compute the same host-side step/reciprocal constants, so
# the oracle cannot diverge from the kernel by a compile-mode ULP (see
# adc_round's docstring).
from repro.kernels.taom_gemm import adc_code, adc_round, adc_step, chunk_fs


def taom_gemm_reference(xq: jnp.ndarray, wq: jnp.ndarray,
                        noise: jnp.ndarray, cfg: PhotonicConfig,
                        adc_fs: float) -> jnp.ndarray:
    """Oracle for kernels.taom_gemm.taom_gemm_quantized.

    Chunks at the exact dpe_size (no lane padding — zero-padding in the
    kernel is a no-op by construction, which this oracle verifies).
    """
    m, k = xq.shape
    _, d = wq.shape
    n = cfg.dpe_size
    n_chunks = max(1, -(-k // n))
    kp = n_chunks * n - k
    x = jnp.pad(xq.astype(jnp.float32), ((0, 0), (0, kp)))
    w = jnp.pad(wq.astype(jnp.float32), ((0, kp), (0, 0)))
    xc = x.reshape(m, n_chunks, n)
    wc = w.reshape(n_chunks, n, d)
    psums = jnp.einsum("mcn,cnd->cmd", xc, wc,
                       preferred_element_type=jnp.float32)    # (C, M, D)
    sigma = detection_sigma(cfg)
    if cfg.backend in (Backend.AMW, Backend.MAW):
        assert noise.shape == (n_chunks, m, d)
        noisy = psums + sigma * noise
        codes = adc_code(noisy, cfg.adc_bits, chunk_fs(cfg))
        return jnp.sum(codes, axis=0) * adc_step(cfg.adc_bits, chunk_fs(cfg))
    assert noise.shape == (m, d)
    acc = jnp.sum(psums, axis=0)
    acc = acc + sigma * math.sqrt(float(n_chunks)) * noise
    return adc_round(acc, cfg.adc_bits, float(adc_fs))


def ssd_scan_reference(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, c: jnp.ndarray,
                       initial_state: jnp.ndarray | None = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Naive sequential Mamba2/SSD recurrence — oracle for kernels.ssd_scan.

    Shapes (single batch element):
      x:  (L, H, P)   input per head (P = head dim)
      dt: (L, H)      softplus-activated step sizes (>0)
      a:  (H,)        negative state decay rate (A = -exp(a_log) outside)
      b:  (L, G, S)   input->state projection (G state groups, S state dim)
      c:  (L, G, S)   state->output projection
    Heads are grouped: head h uses group g = h // (H // G).
    Returns (y: (L, H, P), final_state: (H, P, S)).
    """
    l, h, p = x.shape
    g, s = b.shape[1], b.shape[2]
    heads_per_group = h // g
    state = (jnp.zeros((h, p, s), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))
    ys = []
    for t in range(l):
        dt_t = dt[t]                                   # (H,)
        decay = jnp.exp(dt_t * a)                      # (H,)  a < 0
        bg = b[t]                                      # (G, S)
        cg = c[t]                                      # (G, S)
        b_h = jnp.repeat(bg, heads_per_group, axis=0)  # (H, S)
        c_h = jnp.repeat(cg, heads_per_group, axis=0)  # (H, S)
        # state update: state = decay * state + dt * x_t (outer) b_t
        upd = (dt_t[:, None] * x[t])[:, :, None] * b_h[:, None, :]
        state = decay[:, None, None] * state + upd
        ys.append(jnp.einsum("hps,hs->hp", state, c_h))
    return jnp.stack(ys).astype(x.dtype), state
