"""Plain reference of a photonic CNN configuration, in jax.numpy.

Written from the configuration's stated numerics alone; it imports
nothing of the program.  Per GEMM layer (conv, depthwise conv, fc):

1. Activations are quantized per tensor to ``bits`` (q = clip(round(x /
   s), -qmax, qmax), s = max|x| / qmax, qmax = 2**bits - 1) over the
   values the layer reads: for a strided 1x1 conv, the pixels it
   samples.  Weights are quantized per output channel.
2. The integer dot product over K is cut into chunks of N = ``dpe_size``
   consecutive K indices, K laid out kernel-position-major and
   channel-minor (a depthwise layer's K is kh*kw*C, block-diagonal).
3. Analog-carry backends (heana) add one noise draw of sigma*sqrt(C) per
   output and read the sum once through an ``adc_bits`` ADC of full
   scale qmax**2 * sqrt(K) * 4/3.  Chunk-ADC backends (amw, maw) add a
   noise draw of sigma to every chunk's sum, read each through an ADC of
   full scale qmax**2 * sqrt(N) * 4/3, and add the integer codes.
4. The ADC value is rescaled by the two quantization scales.

Noise: standard normals drawn with ``jax.random.normal`` from
``fold_in(key, gemm_index)``, shaped (rows, D) for analog carry and
(rows, chunks, D) for chunk ADC; rows run over (image, oh, ow).

``dtype`` is the precision of every float value (float32 as the
configuration states; bfloat16 for the control).  Integer dot products
are exact in either and accumulate in float32.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ANALOG_CARRY = ("heana",)
CHUNK_ADC = ("amw", "maw")


def _same_pads(size: int, k: int, stride: int, padding: str):
    if padding != "same":
        return (0, 0)
    o = -(-size // stride)
    total = max((o - 1) * stride + k - size, 0)
    return (total // 2, total - total // 2)


def _quantize(v, bits: int, absmax):
    qmax = (1 << bits) - 1
    scale = jnp.maximum(absmax, 1e-12) * (1.0 / qmax)
    return jnp.clip(jnp.round(v / scale), -qmax, qmax), scale


def _adc_step(adc_bits: int, fs: float) -> float:
    return 2.0 * max(float(fs), 1e-12) / ((1 << adc_bits) - 1)


def _adc_code(v, adc_bits: int, fs: float):
    levels = (1 << adc_bits) - 1
    hi = levels // 2 + levels % 2
    return jnp.clip(jnp.round(v * (1.0 / _adc_step(adc_bits, fs))), -hi, hi)


def _patches(xp, kh: int, kw: int, stride: int, oh: int, ow: int):
    """(B, OH, OW, kh*kw, C) windows of the padded input."""
    return jnp.stack([xp[:, i:i + (oh - 1) * stride + 1:stride,
                         j:j + (ow - 1) * stride + 1:stride, :]
                      for i in range(kh) for j in range(kw)], axis=3)


def _gemm_layer(node: dict, x, w, op: dict, key, gi: int, dtype):
    """One conv / depthwise / fc layer; returns its (B, OH, OW, D) output
    (fc: (B, D))."""
    bits, adc_bits, n = op["bits"], op["adc_bits"], op["dpe_size"]
    backend, sigma = op["backend"], float(op["noise_sigma_int"])
    noisy = bool(op["noise_enabled"])
    qmax = (1 << bits) - 1
    f32 = jnp.float32
    b = x.shape[0]
    kind = node["op"]

    if kind == "fc":
        a = x.reshape(b, -1)
        xq, sx = _quantize(a, bits, jnp.max(jnp.abs(a)))
        k_exec, d = a.shape[1], w.shape[1]
        rows, out_shape = b, (b, d)
    else:
        kh, kw, s = node["kh"], node["kw"], node["stride"]
        _, h, wd, c = x.shape
        ph = _same_pads(h, kh, s, node["padding"])
        pw = _same_pads(wd, kw, s, node["padding"])
        xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
        oh = (h + sum(ph) - kh) // s + 1
        ow = (wd + sum(pw) - kw) // s + 1
        absmax = jnp.max(jnp.stack([
            jnp.max(jnp.abs(xp[:, i:i + (oh - 1) * s + 1:s,
                               j:j + (ow - 1) * s + 1:s, :]))
            for i in range(kh) for j in range(kw)]))
        xq, sx = _quantize(xp, bits, absmax)
        d = c if kind == "depthwise_conv" else w.shape[1]
        k_exec = kh * kw * c
        rows, out_shape = b * oh * ow, (b, oh, ow, d)

    wq, sw = _quantize(w, bits, jnp.max(jnp.abs(w), axis=0, keepdims=True))
    n_chunks = max(1, -(-k_exec // n))
    layer_key = jax.random.fold_in(key, gi) if noisy else None

    if backend in ANALOG_CARRY:
        if kind == "fc":
            acc = jnp.dot(xq, wq, precision=HIGHEST,
                          preferred_element_type=f32)
        else:
            groups = c if kind == "depthwise_conv" else 1
            w_hwio = (wq.reshape(kh, kw, 1, c) if groups > 1
                      else wq.reshape(kh, kw, c, d))
            acc = jax.lax.conv_general_dilated(
                xq, w_hwio, (s, s), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups, precision=HIGHEST,
                preferred_element_type=f32).reshape(rows, d)
        acc = acc.astype(dtype)
        if noisy:
            noise = jax.random.normal(layer_key, (rows, d), f32)
            acc = acc + (sigma * math.sqrt(float(n_chunks))) * noise.astype(
                dtype)
        fs = max(qmax ** 2 * math.sqrt(float(max(k_exec, 1))) * (4.0 / 3.0),
                 1e-6)
        y = _adc_code(acc, adc_bits, fs) * _adc_step(adc_bits, fs)
    elif backend in CHUNK_ADC:
        pad = n_chunks * n - k_exec
        if kind == "depthwise_conv":
            p = _patches(xq, kh, kw, s, oh, ow).reshape(rows, kh * kw, c)
            prod = p * wq[None]                             # (rows, q, c)
            idx = (jnp.arange(kh * kw)[:, None] * c
                   + jnp.arange(c)[None, :]) // n           # chunk of (q, c)
            onehot = (idx[..., None] == jnp.arange(n_chunks)).astype(f32)
            psum = jnp.einsum("mqc,qcj->jmc", prod.astype(f32), onehot,
                              precision=HIGHEST)
        else:
            cols = (xq if kind == "fc" else
                    _patches(xq, kh, kw, s, oh, ow).reshape(rows, k_exec))
            xc = jnp.pad(cols, ((0, 0), (0, pad))).reshape(rows, n_chunks, n)
            wc = jnp.pad(wq, ((0, pad), (0, 0))).reshape(n_chunks, n, d)
            psum = jnp.einsum("mcn,cnd->cmd", xc.astype(f32),
                              wc.astype(f32), precision=HIGHEST)
        psum = psum.astype(dtype)
        if noisy:
            noise = jax.random.normal(layer_key, (rows, n_chunks, d), f32)
            psum = psum + sigma * jnp.moveaxis(noise, 1, 0).astype(dtype)
        fs = max(qmax ** 2 * math.sqrt(float(n)) * (4.0 / 3.0), 1e-6)
        y = (jnp.sum(_adc_code(psum, adc_bits, fs), axis=0)
             * _adc_step(adc_bits, fs))
    else:
        raise ValueError(f"backend {backend!r} has no reference here")
    # The ADC value, rounded as computed, times the product of the two
    # scales: the barriers keep the compiler from reassociating these
    # multiplications (by constants, under whole-program jit), which moves
    # results by a rounding and flips quantization codes downstream.
    y, sx, sw = jax.lax.optimization_barrier((y, sx, sw))
    out = jax.lax.optimization_barrier((y * (sx * sw)).astype(dtype))
    return out.reshape(out_shape)


def _glue(node: dict, a, vals: dict, dtype):
    if node["op"] == "residual_add":
        return a + vals[node["inputs"][1]]
    if node["op"] == "pool" and node["pool"] == "global":
        return jnp.mean(a, axis=(1, 2), keepdims=True).astype(dtype)
    if node["op"] == "pool" and node["pool"] == "max":
        k, s = node["pool_size"], node["pool_stride"]
        return jax.lax.reduce_window(
            a, jnp.array(-jnp.inf, dtype), jax.lax.max, (1, k, k, 1),
            (1, s, s, 1), node["padding"].upper())
    raise ValueError(f"{node['name']}: op {node['op']!r} has no reference")


def forward(config: dict, params: Dict[str, jax.Array], x: jax.Array,
            key: Optional[jax.Array], dtype=jnp.float32) -> jax.Array:
    """Logits (B, classes) of images ``x`` (B, H, W, C)."""
    op = config["operating_point"]
    if op["noise_enabled"] and key is None:
        raise ValueError("a noise-on configuration needs a key")
    vals = {}
    gi = 0
    for node in config["nodes"]:
        if node["op"] == "input":
            vals[node["name"]] = x.astype(dtype)
            continue
        a = vals[node["inputs"][0]]
        if node["op"] in ("conv", "depthwise_conv", "fc"):
            y = _gemm_layer(node, a, params[node["name"]].astype(dtype), op,
                            key, gi, dtype)
            gi += 1
        else:
            y = _glue(node, a, vals, dtype)
        if node.get("relu"):
            y = jnp.maximum(y, 0)
        vals[node["name"]] = y
    return vals[config["nodes"][-1]["name"]].astype(jnp.float32)


def jitted(config: dict, dtype=jnp.float32):
    """``forward`` compiled for one configuration: fn(params, x, key)."""
    return jax.jit(lambda params, x, key: forward(config, params, x, key,
                                                  dtype))
