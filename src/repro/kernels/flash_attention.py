"""Pallas TPU kernel: flash attention (causal / sliding-window, fwd).

Online-softmax tiling for the training/prefill hot path: the (S, S) score
matrix never materializes — running (max, sum, weighted-V) stats live in
VMEM scratch while K/V stream through 128-wide blocks.

Grid: (BH, S/bq, S/bk) with the key axis innermost (sequential); scratch
(m, l, acc) persists across key steps for a fixed query tile.  Causal and
sliding-window masks are applied from global block offsets; fully-masked
key blocks contribute exp(-inf)=0 (correct, if not skipped — block-level
early-exit is a TPU grid limitation; the masking keeps it exact).

Layout contract: q/k/v are (BH, S, D) with heads pre-flattened into the
batch dim (GQA callers expand K/V per head first — same contract as the
model zoo's TP-aligned attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int, s_real: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kj < s_real            # padded keys are never attended
    if causal:
        valid &= kj <= qi
    if window:
        valid &= kj > qi - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (BH, S, D) -> (BH, S, D).  S padded to block multiples."""
    bh, s, d = q.shape
    scale = d ** -0.5
    bq = min(block_q, max(8, s))
    bk = min(block_k, max(8, s))
    sp_q = -(-s // bq) * bq
    sp_k = -(-s // bk) * bk
    sp = max(sp_q, sp_k)
    if sp != s:
        pad = ((0, 0), (0, sp - s), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq, nk = sp // bq, sp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk, s_real=s),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s, :]


def flash_attention_reference(q, k, v, *, causal: bool = True,
                              window: int = 0) -> jnp.ndarray:
    """Dense oracle: (BH, S, D) softmax attention with the same mask."""
    bh, s, d = q.shape
    scores = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    valid = jnp.ones((s, s), bool)
    if causal:
        valid &= kj <= qi
    if window:
        valid &= kj > qi - window
    scores = jnp.where(valid[None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)
