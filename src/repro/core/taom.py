"""TAOM — hybrid Time-Amplitude analog Optical Multiplier (paper §3.2.2).

A TAOM is a single add-drop microring modulator driven by a hybrid
time-amplitude electrical signal:

  * the *weight* w is produced by a DAC as an amplitude-analog level,
  * the *activation* a is produced by a digital pulse converter (DPC) as a
    time-analog pulse width,
  * an RF mixer multiplies them; the MRM transfers the product onto the
    optical carrier, so the *area* of the optical output pulse equals
    a_q * w_q (in integer units after quantization),
  * the sign of the product selects the through (+) or drop (-) port, i.e.
    the result is a *balanced* optical pulse pair.

This module is the explicit device-level model.  ``photonic_gemm`` fuses the
same math for speed; ``tests/test_photonic_gemm.py`` asserts the two paths
agree exactly when noise is disabled.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.types import TAOM_MAX_PULSE_WIDTH_NS, PhotonicConfig


def quantize(x: jnp.ndarray, bits: int, axis=None, keepdims: bool = True,
             eps: float = 1e-12, absmax: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric B-bit quantization: returns integer-valued q and scale s.

    x ~= q * s with q in [-qmax, qmax].  ``axis=None`` => per-tensor scale;
    an int/tuple axis gives per-channel scales (reduced over ``axis``).
    ``absmax`` replaces the max of |x| when the scale belongs to fewer
    values than ``x`` holds (the pixels a strided window samples).
    """
    qmax = (1 << bits) - 1
    if absmax is None:
        absmax = jnp.max(jnp.abs(x), axis=axis,
                         keepdims=(axis is not None) and keepdims)
    # Explicit reciprocal multiply, NOT division by the constant qmax: XLA
    # rewrites x/const into x*(1/const) when jit-compiling whole programs
    # but not op-by-op, so a division here would make jitted and eager
    # forwards differ by 1 ULP in scale — enough to cross a downstream ADC
    # rounding boundary and break the executor's bit-exactness contract.
    scale = jnp.maximum(absmax, eps) * (1.0 / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return q, scale


def encode_time_amplitude(a_q: jnp.ndarray, w_q: jnp.ndarray, bits: int,
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map integer operands to physical drive signals.

    Returns (pulse_width_ns, amplitude_frac): the DPC pulse width carrying
    |a_q| and the DAC amplitude fraction carrying |w_q| (sign tracked by the
    caller through the balanced ports).
    """
    qmax = (1 << bits) - 1
    pulse_width_ns = jnp.abs(a_q) / qmax * TAOM_MAX_PULSE_WIDTH_NS
    amplitude_frac = jnp.abs(w_q) / qmax
    return pulse_width_ns, amplitude_frac


def taom_multiply(a_q: jnp.ndarray, w_q: jnp.ndarray,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Balanced optical pulse areas (through, drop) for integer operands.

    area_through - area_drop == a_q * w_q  (integer product units), with the
    positive part routed to the through port and the negative part to the
    drop port, exactly as the balanced detection in Fig. 4(b) expects.
    """
    prod = a_q * w_q
    through = jnp.maximum(prod, 0.0)
    drop = jnp.maximum(-prod, 0.0)
    return through, drop


def taom_array_products(a_q: jnp.ndarray, w_q: jnp.ndarray,
                        cfg: PhotonicConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Products of a spectrally hitless TAOM array.

    a_q, w_q: (..., n) integer operand vectors, one entry per wavelength.
    Returns the (through, drop) pulse-area vectors that the aggregation
    lanes deliver to the BPCA.  The hitless arrangement means no crosstalk
    term couples entries — products are exact per wavelength (the paper's
    point: crosstalk is eliminated structurally, and shows up only in the
    link-budget penalty used by the scalability analysis).
    """
    del cfg  # hitless: no crosstalk coupling term
    return taom_multiply(a_q, w_q)
