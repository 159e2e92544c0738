"""Pallas TPU kernel: depthwise convolution for the analog-carry policies.

A depthwise layer's C channels are C grouped (kh*kw, 1) dot products.
Under an analog-carry policy (HEANA, ``*_bpca``) the chunk layout of K
does not change the result: each output is the exact integer sum of its
kh*kw products, one noise draw and one ADC read.  So this kernel reads
the kh*kw shifted windows of the padded activation codes and multiplies
each by its tap's weight codes, building no im2col in HBM, and gives
bitwise what ``taom_gemm_quantized`` gives on the block-diagonal
(kh*kw*C, C) GEMM of the same layer:

  * the integer sum is exact in float32 in any order;
  * noise is sigma * sqrt(ceil(kh*kw*C / N)) per output, the block-
    diagonal GEMM's chunk count (its K is kh*kw*C);
  * the ADC reads at the full scale the caller passes
    (``calibrated_adc_fs(kh*kw*C)`` in ``kernels.ops``).

Layout: the padded codes are read in place.  A grid step takes a window
of input rows that overlaps the next step's by the kernel's halo
(``pl.Element`` offsets, so no halo is copied in HBM), all the columns,
and one lane block of channels; a stride-s tap is a sublane-strided
read.  Grid: (image groups, row tiles, channel blocks), all parallel;
inside a step a loop walks the output rows.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.photonic_gemm import detection_sigma
from repro.core.types import PhotonicConfig
from repro.kernels.taom_gemm import (LANE, SUBLANE, VMEM_BUDGET_BYTES,
                                     _round_up, adc_round)

#: Channel blocks the kernel takes beyond one lane block (128 lanes).
BLOCK_C_CANDIDATES = (128, 256, 384, 512)
#: Output pixels per grid step the scheduler asks for; the kernel takes
#: the largest whole rows (or whole images) under it that fit VMEM.
BLOCK_M_DEFAULT = 4096
# Loop temporaries of one output row (accumulator, tap window, product,
# ADC code), in (row, channel block) f32 tiles.
_ROW_TEMPS = 4


def out_dims(hp: int, wp: int, kh: int, kw: int,
             stride: int) -> Tuple[int, int]:
    """Output extent of a 'valid' window over the padded input."""
    return (hp - kh) // stride + 1, (wp - kw) // stride + 1


def block_c(c: int, block_d: int, stride: int) -> int:
    """The channel block the kernel runs: all channels up to one lane
    block, else ``block_d`` (a multiple of 128), or one lane block at a
    stride above 1 (the TPU compiler takes sublane-strided reads from
    128-lane rows only)."""
    if c <= LANE:
        return c
    return min(block_d, _round_up(c, LANE)) if stride == 1 else LANE


def choose_block_c(c: int) -> int:
    """Least channel padding, then the larger block."""
    if c <= LANE:
        return c
    return min(BLOCK_C_CANDIDATES,
               key=lambda b: (_round_up(c, b), -b))


def in_window(th: int, ow: int, kh: int, kw: int,
              stride: int) -> Tuple[int, int]:
    """Input rows and columns that ``th`` rows of ``ow`` outputs read."""
    return (th - 1) * stride + kh, (ow - 1) * stride + kw


def vmem_bytes(nb: int, th: int, ow: int, bc: int, kh: int, kw: int,
               stride: int, noise: bool) -> int:
    """VMEM footprint of one grid step: the x (nb, rows in, columns in,
    bc), weight, noise and output blocks, f32 and double-buffered, with
    their (8, 128) tile padding, plus one row's loop temporaries."""
    lanes = _round_up(bc, LANE)
    rows_in, cols_in = in_window(th, ow, kh, kw, stride)
    x = nb * rows_in * _round_up(cols_in, SUBLANE) * lanes
    out = nb * th * _round_up(ow, SUBLANE) * lanes
    w = _round_up(kh * kw, SUBLANE) * lanes
    blocks = x + w + out * (2 if noise else 1)
    return int(4 * (2 * blocks + _ROW_TEMPS * _round_up(ow, SUBLANE) * lanes))


def step_shape(block_m: int, b: int, oh: int, ow: int, bc: int, kh: int,
               kw: int, stride: int, noise: bool) -> Tuple[int, int]:
    """(images, output rows) per grid step: the most pixels up to
    ``block_m`` in whole rows, or in whole images when one fits, whose
    ``vmem_bytes`` fits ``VMEM_BUDGET_BYTES``.  Rows are then balanced
    over the row tiles, so the last tile is not mostly padding."""
    def fits(nb, th):
        return vmem_bytes(nb, th, ow, bc, kh, kw, stride,
                          noise) <= VMEM_BUDGET_BYTES

    nb, th = 1, max(1, min(oh, block_m // ow))
    if th == oh:
        nb = max(d for d in range(1, b + 1)
                 if b % d == 0 and d * oh * ow <= max(block_m, oh * ow))
        while nb > 1 and not fits(nb, th):
            nb = max(d for d in range(1, nb) if b % d == 0)
    while th > 1 and not fits(nb, th):
        th -= 1
    if not fits(nb, th):
        raise ValueError(
            f"no depthwise step fits the {VMEM_BUDGET_BYTES} B VMEM budget "
            f"at width {ow} and channel block {bc}")
    tiles = -(-oh // th)
    return nb, -(-oh // tiles)


def _kernel(x_ref, w_ref, *refs, taps, nb: int, th: int, ow: int,
            stride: int, noise_scale: Optional[float], adc_bits: int,
            adc_fs: float):
    """One step: ``nb`` images x ``th`` output rows x one channel block.

    Each output row starts from zero and adds its kh*kw tap products
    (exact integers), then the scaled noise, then the ADC read."""
    noise_ref = refs[0] if noise_scale is not None else None
    out_ref = refs[-1]
    wv = w_ref[...]

    def row(t, carry):
        n, r = t // th, t % th
        acc = jnp.zeros(out_ref.shape[2:], jnp.float32)
        for q, (i, j) in enumerate(taps):
            acc = acc + (x_ref[n, r * stride + i, pl.ds(j, ow, stride), :]
                         * wv[q:q + 1, :])
        if noise_ref is not None:
            acc = acc + noise_scale * noise_ref[n, r]
        out_ref[n, r] = adc_round(acc, adc_bits, adc_fs)
        return carry

    jax.lax.fori_loop(0, nb * th, row, 0)


def taom_depthwise_quantized(xp: jnp.ndarray, wq: jnp.ndarray,
                             noise: Optional[jnp.ndarray] = None, *,
                             cfg: PhotonicConfig, adc_fs: float,
                             kh: int, kw: int, stride: int = 1,
                             block_m: int = BLOCK_M_DEFAULT,
                             block_d: int = LANE,
                             interpret: bool = False) -> jnp.ndarray:
    """Depthwise photonic convolution on pre-quantized codes.

    xp: (B, Hp, Wp, C) padded activation codes (integer-valued f32);
    wq: (kh*kw, C) weight codes, tap-major (row i*kw + j is tap (i, j));
    noise: standard normal (B, OH, OW, C), or None with noise off.
    Returns the ADC value (B, OH, OW, C) in integer product units; the
    caller applies the scales.  ``block_m`` bounds the output pixels per
    grid step, ``block_d`` is the channel block (see ``block_c``).
    """
    b, hp, wp, c = xp.shape
    oh, ow = out_dims(hp, wp, kh, kw, stride)
    bc = block_c(c, block_d, stride)
    cp = _round_up(c, bc)
    nb, th = step_shape(block_m, b, oh, ow, bc, kh, kw, stride,
                        noise is not None)
    tiles = -(-oh // th)
    rows_in, cols_in = in_window(th, ow, kh, kw, stride)
    # Rows for every tile's window (the last tile's extra output rows
    # read zeros and are sliced off); the columns the windows read.
    rows = (tiles - 1) * th * stride + rows_in
    x = jnp.pad(xp.astype(jnp.float32),
                ((0, 0), (0, max(0, rows - hp)), (0, 0), (0, cp - c)))
    x = x[:, :, :cols_in]
    w = jnp.pad(wq.astype(jnp.float32), ((0, 0), (0, cp - c)))
    taps = tuple((i, j) for i in range(kh) for j in range(kw))

    n_chunks = max(1, -(-(kh * kw * c) // cfg.dpe_size))
    out_block = pl.BlockSpec((nb, th, ow, bc), lambda i, r, k: (i, r, 0, k))
    # Element offsets on every dimension (the TPU pipeline takes all or
    # none); a lone channel block is offset 0, which the compiler can
    # prove lane-aligned.
    in_specs = [
        pl.BlockSpec(tuple(map(pl.Element, (nb, rows_in, cols_in, bc))),
                     lambda i, r, k: (i * nb, r * th * stride, 0,
                                      k * bc if cp > bc else 0)),
        pl.BlockSpec((kh * kw, bc), lambda i, r, k: (0, k)),
    ]
    operands = [x, w]
    noise_scale = None
    if noise is not None:
        assert noise.shape == (b, oh, ow, c), noise.shape
        noise_scale = detection_sigma(cfg) * math.sqrt(float(n_chunks))
        operands.append(jnp.pad(noise.astype(jnp.float32),
                                ((0, 0), (0, tiles * th - oh), (0, 0),
                                 (0, cp - c))))
        in_specs.append(out_block)

    kern = functools.partial(_kernel, taps=taps, nb=nb, th=th, ow=ow,
                             stride=stride, noise_scale=noise_scale,
                             adc_bits=cfg.adc_bits, adc_fs=adc_fs)
    out = pl.pallas_call(
        kern,
        grid=(b // nb, tiles, cp // bc),
        in_specs=in_specs,
        out_specs=out_block,
        out_shape=jax.ShapeDtypeStruct((b, tiles * th, ow, cp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        interpret=interpret,
        name="taom_depthwise",
    )(*operands)
    return out[:, :oh, :, :c]
