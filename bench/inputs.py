"""Weights, images and noise keys made from ``--seed``.

The weights are made on the device in one jitted call.  The images are
host ``numpy`` arrays, made before the window, so that each request's
transfer to the device happens inside it, as it does for a user.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import network


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed below 2**62."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0x7FFFFFFF, seed >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def weight_shapes(config: dict) -> Tuple[Tuple[str, Tuple[int, int]], ...]:
    """(name, (K, D)) of every GEMM weight; a depthwise layer's is the
    compact (kh*kw, C)."""
    shapes = network.infer_shapes(config["nodes"], config["input_hw"])
    out = []
    for n in config["nodes"]:
        if n["op"] not in ("conv", "depthwise_conv", "fc"):
            continue
        ih, iw, ic = shapes[n["inputs"][0]]
        if n["op"] == "conv":
            shape = (n["kh"] * n["kw"] * ic, n["cout"])
        elif n["op"] == "depthwise_conv":
            shape = (n["kh"] * n["kw"], ic)
        else:
            shape = (ih * iw * ic, n["cout"])
        out.append((n["name"], shape))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=1)
def _make_weights(key, shapes):
    return {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32) * (1.0 / math.sqrt(shape[0]))
            for i, (name, shape) in enumerate(shapes)}


def weights(config: dict, seed: int) -> Dict[str, jax.Array]:
    """Float32 weights, normal(0, 1/fan_in), on the default device."""
    key = jax.random.fold_in(seed_key(seed), 1)
    return _make_weights(key, weight_shapes(config))


def images(config: dict, seed: int, n: int) -> np.ndarray:
    """``n`` distinct standard-normal images (n, H, W, C), float32."""
    h, w = config["input_hw"]
    c = config["nodes"][0]["cout"]
    rng = np.random.default_rng([seed, 2])
    return rng.standard_normal((n, h, w, c), dtype=np.float32)


def noise_root(seed: int) -> jax.Array:
    """Root of the per-request noise keys: request i uses fold_in(root, i)."""
    return jax.random.fold_in(seed_key(seed), 3)
