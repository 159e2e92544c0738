"""Pallas TPU kernel: HEANA TAOM-array GEMM with BPCA accumulation policy.

Maps the paper's DPU dataflow onto the TPU memory hierarchy:

  * one DPE chunk (N = cfg.dpe_size wavelengths, zero-padded to the 128-wide
    MXU lane boundary) == one K-step of the kernel grid == one temporal fold;
  * the VMEM scratch accumulator == the BPCA capacitor: psums accrue across
    K-steps without leaving VMEM (HEANA policy: no per-chunk ADC, no psum
    buffer traffic — exactly the paper's point, restated for a TPU);
  * the AMW/MAW policy rounds every chunk psum through the ADC before the
    digital add, which the kernel reproduces in-loop (noise interacts with
    rounding, so it cannot be folded into the final draw);
  * detection noise is pre-sampled standard normal (PRNG stays outside the
    kernel), scaled by the link-budget sigma inside;
  * the ADC full scale is a *calibrated* scalar (programmable-gain setting),
    like real analog frontends — no data-dependent global max inside.

Zero-padding faithfulness: padded lanes contribute 0 to the integer psum and
do not move ADC rounding boundaries, so kernel results equal the pure-jnp
oracle (kernels/ref.py) that chunks at the exact dpe_size.

Grid: (M/bm, D/bd, C) with C innermost (sequential), so the accumulator
persists across chunk steps for a fixed output tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.types import Backend, PhotonicConfig

LANE = 128
SUBLANE = 8

#: Scoped VMEM the kernel is compiled under (``vmem_limit_bytes``) and
#: that exec.scheduler.choose_tile admits tiles against.  16 MiB is the
#: v5e compiler's default scoped limit.  Stating it explicitly makes the
#: kernel's own footprint the only thing that decides whether a tile
#: compiles: under the implicit default the compiler refused some tiles
#: depending on the surrounding program (memory-space assignment of the
#: custom call's operands), not on the kernel.
VMEM_BUDGET_BYTES = 16 * 2 ** 20
# Readout temporaries beyond the blocks (the noisy accumulator and its
# ADC-rounded value), in (block_m, block_d) f32 tiles.  v5e compiles put
# the largest at 1.41 tiles (analog-carry readout at (4096, 256));
# tests/test_chip_compile.py compiles the largest admitted tiles.
_READOUT_TILES = 1.5


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def vmem_bytes(block_m: int, block_d: int, dpe_size: int) -> int:
    """VMEM footprint of one grid step of ``taom_gemm_quantized``.

    Counts the kernel's BlockSpecs — x (1, bm, slot), w (1, slot, bd),
    noise (bm, bd) and out (bm, bd), all f32 and double-buffered by the
    pipeline — plus the (bm, bd) accumulator scratch and the readout
    temporaries.  ``block_m``/``block_d`` are the tiles the kernel runs
    (after its clamping to the GEMM), ``slot`` the lane-padded chunk.
    """
    slot = _round_up(dpe_size, LANE)
    tile = block_m * block_d
    blocks = block_m * slot + slot * block_d + 2 * tile
    return int(4 * (2 * blocks + (1 + _READOUT_TILES) * tile))


def adc_round(v: jnp.ndarray, adc_bits: int, full_scale: float) -> jnp.ndarray:
    """Uniform mid-tread ADC over [-fs, fs] — mirrors core.bpca.adc_readout.

    ``full_scale`` is a PYTHON float (the calibrated PGA setting), so both
    ``step`` and its reciprocal are computed host-side in double precision
    and enter the traced program as multiply-by-constant only.  A traced
    ``v / step`` would be rewritten to a reciprocal multiply by XLA under
    whole-program jit but not eagerly, making compiled and eager forwards
    disagree by 1 ULP right at ADC rounding boundaries — this formulation
    is bit-identical under both, and kernels/ref.py shares this exact
    function so kernel and oracle cannot diverge either.
    """
    return adc_code(v, adc_bits, full_scale) * adc_step(adc_bits, full_scale)


def adc_step(adc_bits: int, full_scale: float) -> float:
    """One ADC code's width, a host-side double (see ``adc_round``)."""
    # Same degenerate-input floor as core.bpca.adc_readout: a zero/negative
    # programmed full scale clamps instead of dividing by zero.
    return 2.0 * max(float(full_scale), 1e-12) / ((1 << adc_bits) - 1)


def adc_code(v: jnp.ndarray, adc_bits: int, full_scale: float) -> jnp.ndarray:
    """The ADC's output code for ``v``: integer-valued f32 in [-hi, hi]."""
    levels = (1 << adc_bits) - 1
    hi = levels // 2 + levels % 2
    return jnp.clip(jnp.round(v * (1.0 / adc_step(adc_bits, full_scale))),
                    -hi, hi)


def calibrated_adc_fs(k: int, cfg: PhotonicConfig) -> float:
    """Analytic PGA calibration: ~4 sigma of a random-+/- integer dot walk."""
    qmax = float(cfg.qmax)
    return max(qmax ** 2 * math.sqrt(float(max(k, 1))) * (4.0 / 3.0), 1e-6)


def chunk_fs(cfg: PhotonicConfig) -> float:
    """Per-chunk ADC full scale for the AMW/MAW per-psum conversion."""
    qmax = float(cfg.qmax)
    return max(qmax ** 2 * math.sqrt(float(cfg.dpe_size)) * (4.0 / 3.0), 1e-6)


def _kernel_analog_carry(x_ref, w_ref, noise_ref, out_ref, acc_ref, *,
                         n_chunks: int, sigma: float, adc_bits: int,
                         adc_fs: float):
    """HEANA / *_bpca policy: analog accumulate, one noise draw + one ADC."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(c == n_chunks - 1)
    def _readout():
        acc = acc_ref[...]
        acc = acc + (sigma * math.sqrt(float(n_chunks))) * noise_ref[...]
        out_ref[...] = adc_round(acc, adc_bits, adc_fs)


def _kernel_chunk_adc(x_ref, w_ref, noise_ref, out_ref, acc_ref, *,
                      n_chunks: int, sigma: float, adc_bits: int,
                      fs_chunk: float):
    """AMW/MAW policy: per-chunk noise + ADC rounding, digital reduction.

    The digital adder sums integer ADC codes, scaled by the code width
    once at readout: the sum is exact in any order, so the oracle's
    reduction cannot differ from the kernel's in the last bits.
    """
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    psum = jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32)
    acc_ref[...] += adc_code(psum + sigma * noise_ref[0], adc_bits, fs_chunk)

    @pl.when(c == n_chunks - 1)
    def _readout():
        out_ref[...] = acc_ref[...] * adc_step(adc_bits, fs_chunk)


def taom_gemm_quantized(xq: jnp.ndarray, wq: jnp.ndarray,
                        noise: jnp.ndarray, cfg: PhotonicConfig,
                        adc_fs: float,
                        *, block_m: int = 128, block_d: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """Chunked photonic GEMM on pre-quantized integer-valued f32 operands.

    xq: (M, K); wq: (K, D) — integer-valued f32 (from core.taom.quantize).
    noise: standard normal — (M, D) for analog-carry backends,
    (C, M, D) for chunk-ADC backends (C = ceil(K / dpe_size)).
    Returns the integer-unit accumulation (M, D); caller applies scales.
    """
    m, k = xq.shape
    k2, d = wq.shape
    assert k == k2, (k, k2)
    n = cfg.dpe_size
    n_chunks = max(1, -(-k // n))

    # Lay K out as C lane-aligned chunk slots, zero-padded per slot.
    slot = _round_up(n, LANE)
    kpad = n_chunks * n - k
    xpad = jnp.pad(xq.astype(jnp.float32), ((0, 0), (0, kpad)))
    wpad = jnp.pad(wq.astype(jnp.float32), ((0, kpad), (0, 0)))
    xq_c = jnp.pad(xpad.reshape(m, n_chunks, n),
                   ((0, 0), (0, 0), (0, slot - n)))            # (M, C, slot)
    wq_c = jnp.pad(wpad.reshape(n_chunks, n, d),
                   ((0, 0), (0, slot - n), (0, 0)))            # (C, slot, D)

    # Pad M/D to block multiples.
    bm = min(block_m, _round_up(m, SUBLANE))
    bd = min(block_d, _round_up(d, LANE))
    mp, dp = _round_up(m, bm), _round_up(d, bd)
    xq_c = jnp.pad(xq_c, ((0, mp - m), (0, 0), (0, 0)))
    wq_c = jnp.pad(wq_c, ((0, 0), (0, 0), (0, dp - d)))
    x2 = xq_c.transpose(1, 0, 2)                               # (C, M, slot)

    chunk_adc = cfg.backend in (Backend.AMW, Backend.MAW)
    if chunk_adc:
        assert noise.shape == (n_chunks, m, d), noise.shape
        noise_p = jnp.pad(noise.astype(jnp.float32),
                          ((0, 0), (0, mp - m), (0, dp - d)))
        noise_spec = pl.BlockSpec((1, bm, bd), lambda i, j, c: (c, i, j))
    else:
        assert noise.shape == (m, d), noise.shape
        noise_p = jnp.pad(noise.astype(jnp.float32),
                          ((0, mp - m), (0, dp - d)))
        noise_spec = pl.BlockSpec((bm, bd), lambda i, j, c: (i, j))

    from repro.core.photonic_gemm import detection_sigma
    sigma = detection_sigma(cfg)

    if chunk_adc:
        name = "taom_chunk_adc"
        kern = functools.partial(
            _kernel_chunk_adc, n_chunks=n_chunks, sigma=sigma,
            adc_bits=cfg.adc_bits, fs_chunk=chunk_fs(cfg))
    else:
        name = "taom_analog_carry"
        kern = functools.partial(
            _kernel_analog_carry, n_chunks=n_chunks, sigma=sigma,
            adc_bits=cfg.adc_bits, adc_fs=adc_fs)

    out = pl.pallas_call(
        kern,
        grid=(mp // bm, dp // bd, n_chunks),
        in_specs=[
            pl.BlockSpec((1, bm, slot), lambda i, j, c: (c, i, 0)),
            pl.BlockSpec((1, slot, bd), lambda i, j, c: (c, 0, j)),
            noise_spec,
        ],
        out_specs=pl.BlockSpec((bm, bd), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        interpret=interpret,
        name=name,
    )(x2, wq_c, noise_p)
    return out[:m, :d]
