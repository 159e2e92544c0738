"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

The SSD decomposition splits the sequence into chunks of length Q:

  * intra-chunk: a (Q, Q) causal "attention-like" block — MXU-friendly
    matmuls (C B^T masked by the decay kernel L);
  * inter-chunk: a (P, S) running state carried across chunks — lives in
    VMEM scratch, updated once per chunk step (the sequential recurrence is
    hoisted from per-token to per-chunk, exactly the paper's trick in
    arXiv:2405.21060, adapted to TPU: chunk length 128 keeps both matmul
    operands MXU-aligned while the state never leaves VMEM).

Grid: (BH, L/Q) with the chunk axis innermost/sequential. Head groups are
expanded to per-head B/C *outside* the kernel (G -> H), keeping the body a
dense per-head computation.

Decay exponents are always <= 0 (dt > 0, a < 0), so every exp() here is in
(0, 1] — numerically safe in f32 without max-subtraction tricks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, n_chunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)          # (Q,)
    a = a_ref[0, 0].astype(jnp.float32)         # scalar
    b = b_ref[0].astype(jnp.float32)            # (Q, S)
    c = c_ref[0].astype(jnp.float32)            # (Q, S)

    da = dt * a                                  # (Q,) each <= 0
    cum = jnp.cumsum(da)                         # (Q,) decreasing
    q = x.shape[0]

    # Intra-chunk: scores[t, s] = (c_t . b_s) * exp(cum_t - cum_s) * dt_s,
    # causal (s <= t).
    seg = cum[:, None] - cum[None, :]            # (Q, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.where(row >= col, jnp.exp(seg) * dt[None, :], 0.0)
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32) * lmat
    y_intra = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # Inter-chunk: contribution of the carried state.
    state = state_ref[...]                       # (P, S)
    y_inter = jnp.dot(c, state.T,
                      preferred_element_type=jnp.float32) * \
        jnp.exp(cum)[:, None]                    # (Q, P)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: state' = exp(cum_end) state + sum_s exp(cum_end - cum_s)
    #                         dt_s x_s (outer) b_s
    carry_decay = jnp.exp(cum[-1])
    w = jnp.exp(cum[-1] - cum) * dt              # (Q,)
    state_ref[...] = carry_decay * state + jnp.dot(
        (w[:, None] * x).T, b, preferred_element_type=jnp.float32)

    @pl.when(c_idx == n_chunks - 1)
    def _final():
        state_out_ref[0] = state_ref[...].astype(state_out_ref.dtype)


def ssd_scan_chunked(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                     b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 128,
                     interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan over flattened (batch*head) sequences.

    x: (BH, L, P); dt: (BH, L); a: (BH,); b, c: (BH, L, S), already
    head-expanded.  L must be divisible by ``chunk`` (caller pads).
    Returns (y: (BH, L, P), final_state: (BH, P, S)).
    """
    bh, l, p = x.shape
    s = b.shape[-1]
    assert l % chunk == 0, (l, chunk)
    n_chunks = l // chunk
    a2 = a.reshape(bh, 1).astype(jnp.float32)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks),
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, chunk), lambda i, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, k: (i, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, p, s), lambda i, k: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a2, b, c)
    return y, state
