"""Public jit'd wrappers around the Pallas kernels, with dispatch + VJPs.

``photonic_matmul`` is what the model zoo calls: it quantizes, picks the
kernel or the pure-jnp oracle (kernels run in interpret mode on CPU), and
attaches the straight-through-estimator VJP so photonic numerics are
trainable.  ``photonic_depthwise`` does the same for a depthwise layer
under an analog-carry policy, bitwise its block-diagonal GEMM.

``ssd_scan`` is the Mamba2 scan entry point: the Pallas kernel for the
serving/prefill hot path, and a differentiable chunked jnp implementation
(same math, jax.lax.scan over chunks) for training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.photonic_gemm import (ANALOG_CARRY_BACKENDS, noise_shape,
                                     sample_noise)
from repro.core.taom import quantize
from repro.core.types import Backend, PhotonicConfig
from repro.kernels import ref as ref_mod
from repro.kernels import ssd_scan as ssd_kernel_mod
from repro.kernels import taom_depthwise as taom_dw_kernel_mod
from repro.kernels import taom_gemm as taom_kernel_mod
from repro.models.lowering import conv_out_dim, pad_window


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# photonic_matmul
# ---------------------------------------------------------------------------
def _taom_forward(x2d: jnp.ndarray, w: jnp.ndarray, noise: jnp.ndarray,
                  cfg: PhotonicConfig, adc_fs: float, impl: str,
                  blocks: tuple, mesh: Optional[Mesh]) -> jnp.ndarray:
    f32 = jnp.float32
    xq, sx = quantize(x2d.astype(f32), cfg.bits, axis=None)
    wq, sw = quantize(w.astype(f32), cfg.bits, axis=0)
    if impl == "pallas":
        kernel = functools.partial(
            taom_kernel_mod.taom_gemm_quantized, cfg=cfg, adc_fs=adc_fs,
            block_m=blocks[0], block_d=blocks[1], interpret=_on_cpu())
        if mesh is not None:
            # XLA cannot partition a Mosaic kernel: each device runs it on
            # its own rows.  Rows are independent, so this is bitwise the
            # unsharded kernel; the quantize max above stays global.
            rows = P(mesh.axis_names)
            kernel = jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(rows, P(), rows if noise.ndim == 2
                          else P(None, mesh.axis_names)),
                out_specs=rows, check_vma=False)
        acc = kernel(xq, wq, noise)
    else:
        acc = ref_mod.taom_gemm_reference(xq, wq, noise, cfg, adc_fs)
    # Pin the rescale against XLA's algebraic simplifier: under
    # whole-program jit it reassociates this multiply chain with the ADC's
    # trailing *step (a splat constant) and with the quantize-scale chain,
    # shifting results by 1 ULP vs the op-by-op eager path — which then
    # crosses ADC rounding boundaries in later layers.  The barriers make
    # the compiled forward bit-identical to eager execution (the
    # executor's compiled-vs-eager contract; free at runtime).
    acc, sx, sw = jax.lax.optimization_barrier((acc, sx, sw))
    out = (acc * (sx * sw)).astype(x2d.dtype)
    return jax.lax.optimization_barrier(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _taom_ste(x2d, w, noise, cfg, adc_fs, impl, blocks, mesh):
    return _taom_forward(x2d, w, noise, cfg, adc_fs, impl, blocks, mesh)


def _taom_ste_fwd(x2d, w, noise, cfg, adc_fs, impl, blocks, mesh):
    return (_taom_forward(x2d, w, noise, cfg, adc_fs, impl, blocks, mesh),
            (x2d, w))


def _taom_ste_bwd(cfg, adc_fs, impl, blocks, mesh, res, g):
    x2d, w = res
    return (g @ w.T).astype(x2d.dtype), (x2d.T @ g).astype(w.dtype), None


_taom_ste.defvjp(_taom_ste_fwd, _taom_ste_bwd)


def photonic_matmul(x: jnp.ndarray, w: jnp.ndarray, cfg: PhotonicConfig,
                    key: Optional[jax.Array] = None,
                    impl: str = "auto",
                    adc_fs: Optional[float] = None,
                    block_m: int = 128, block_d: int = 128,
                    mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """Photonic-numerics matmul: (..., K) @ (K, D) -> (..., D).

    Arbitrary leading batch dims fold into the GEMM M axis (the
    batch-serving shape: Toeplitz rows of every image concatenated), which
    is exactly how the perf model accounts batched CNN layers.

    impl: 'pallas' | 'ref' | 'auto' (pallas kernel, interpreted on CPU).
    adc_fs: calibrated PGA full scale; default = analytic calibration.
    block_m/block_d: kernel output-tile sizes (a LayerPlan's tiling choice
    from repro.exec.scheduler plugs in here; numerics are tile-invariant).
    mesh: when the rows of ``x`` are sharded over this mesh (data-parallel
    serving), the Pallas kernel runs per device on its rows.

    jit-friendly: every branch here is on static config (cfg, impl, key
    being None), so the whole call traces into one compiled program —
    repro.exec.executor.forward_fn wraps an entire CNN of these in a
    single jax.jit.

    Noise contract: ``cfg.noise_enabled=True`` REQUIRES a PRNG key.  The
    old behavior (silently running noiseless when key=None) handed a user
    expecting noisy inference deterministic results with no signal that
    anything was off; now that combination raises — disable noise
    explicitly (cfg.noise_enabled=False) to run deterministically.  The
    EXACT backend is exempt: it bypasses the photonic pipeline entirely
    (no detectors exist to be noisy), so ``noise_enabled`` does not apply.
    """
    if cfg.backend == Backend.EXACT:
        return x @ w
    if cfg.noise_enabled and key is None:
        raise ValueError(
            "photonic_matmul: cfg.noise_enabled=True but key=None — "
            "detection noise needs a PRNG key.  Pass key=jax.random."
            "PRNGKey(...) for noisy inference, or set "
            "noise_enabled=False to run deterministically (the old "
            "behavior silently did the latter).")
    if impl == "auto":
        impl = "pallas"
    if adc_fs is None:
        adc_fs = taom_kernel_mod.calibrated_adc_fs(x.shape[-1], cfg)
    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    if key is not None and cfg.noise_enabled:
        noise = sample_noise(key, x2d.shape, w.shape, cfg)
    else:
        noise = jnp.zeros(noise_shape(x2d.shape, w.shape, cfg), jnp.float32)
    if cfg.backend in (Backend.AMW, Backend.MAW):
        noise = jnp.moveaxis(noise, -2, 0)   # (..., C, D) -> (C, M, D)
    out = _taom_ste(x2d, w, noise, cfg, float(adc_fs), impl,
                    (int(block_m), int(block_d)), mesh)
    return out.reshape(*batch_shape, w.shape[-1])


# ---------------------------------------------------------------------------
# photonic_depthwise
# ---------------------------------------------------------------------------
def _sampled_absmax(xp: jnp.ndarray, kh: int, kw: int, stride: int,
                    oh: int, ow: int) -> jnp.ndarray:
    """max |x| over the padded pixels the layer's windows read: the
    values its block-diagonal GEMM's im2col holds.  With the stride at
    most the window, they are one rectangle from the top-left corner."""
    s = stride
    if s <= kh and s <= kw:
        return jnp.max(jnp.abs(xp[:, :(oh - 1) * s + kh, :(ow - 1) * s + kw]))
    return jnp.max(jnp.stack([
        jnp.max(jnp.abs(xp[:, i:i + (oh - 1) * s + 1:s,
                           j:j + (ow - 1) * s + 1:s]))
        for i in range(kh) for j in range(kw)]))


def _depthwise_forward(x, w, noise, cfg: PhotonicConfig, adc_fs: float,
                       impl: str, geometry: tuple, blocks: tuple,
                       mesh: Optional[Mesh]) -> jnp.ndarray:
    kh, kw, stride, padding = geometry
    f32 = jnp.float32
    xp, (oh, ow) = pad_window(x.astype(f32), kh, kw, stride, padding)
    xq, sx = quantize(xp, cfg.bits, absmax=_sampled_absmax(
        xp, kh, kw, stride, oh, ow))
    wq, sw = quantize(w.astype(f32), cfg.bits, axis=0)
    operands = (xq, wq) if noise is None else (xq, wq, noise)
    if impl == "pallas":
        kernel = functools.partial(
            taom_dw_kernel_mod.taom_depthwise_quantized, cfg=cfg,
            adc_fs=adc_fs, kh=kh, kw=kw, stride=stride, block_m=blocks[0],
            block_d=blocks[1], interpret=_on_cpu())
        if mesh is not None:
            # Images are independent: each device runs the kernel on its
            # own; the quantize max above stays global.
            images = P(mesh.axis_names)
            kernel = jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(images, P(), images)[:len(operands)],
                out_specs=images, check_vma=False)
        acc = kernel(*operands)
    else:
        acc = ref_mod.taom_depthwise_reference(
            *operands, cfg=cfg, adc_fs=adc_fs, kh=kh, kw=kw, stride=stride)
    # The same barriers and rescale as _taom_forward: the block-diagonal
    # GEMM of this layer gives these bits.
    acc, sx, sw = jax.lax.optimization_barrier((acc, sx, sw))
    out = (acc * (sx * sw)).astype(x.dtype)
    return jax.lax.optimization_barrier(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _depthwise_ste(x, w, noise, cfg, adc_fs, impl, geometry, blocks, mesh):
    return _depthwise_forward(x, w, noise, cfg, adc_fs, impl, geometry,
                              blocks, mesh)


def _depthwise_ste_fwd(x, w, noise, cfg, adc_fs, impl, geometry, blocks,
                       mesh):
    return (_depthwise_forward(x, w, noise, cfg, adc_fs, impl, geometry,
                               blocks, mesh), (x, w))


def _depthwise_ste_bwd(cfg, adc_fs, impl, geometry, blocks, mesh, res, g):
    x, w = res
    kh, kw, stride, padding = geometry

    def exact(x, w):
        return jax.lax.conv_general_dilated(
            x, w.reshape(kh, kw, 1, w.shape[-1]), (stride, stride),
            padding.upper(), dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1])

    dx, dw = jax.vjp(exact, x, w)[1](g.astype(x.dtype))
    return dx, dw, None


_depthwise_ste.defvjp(_depthwise_ste_fwd, _depthwise_ste_bwd)


def photonic_depthwise(x: jnp.ndarray, w: jnp.ndarray, cfg: PhotonicConfig,
                       key: Optional[jax.Array] = None, impl: str = "auto",
                       *, kh: int, kw: int,
                       stride: int = 1, padding: str = "same",
                       block_m: int = taom_dw_kernel_mod.BLOCK_M_DEFAULT,
                       block_d: int = 128,
                       mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """Photonic-numerics depthwise conv: (B, H, W, C) images with a
    (kh*kw, C) tap-major weight -> (B, OH, OW, C), for the analog-carry
    policies (HEANA, ``*_bpca``).

    Bitwise what ``photonic_matmul`` gives on the layer's im2col and
    block-diagonal (kh*kw*C, C) weight, without building either: the
    activation scale is the max over the pixels the windows read, the
    weight scales are per channel, the noise is drawn with the block-
    diagonal GEMM's shapes (the same numbers for the same key), the ADC
    full scale is ``calibrated_adc_fs(kh*kw*C)``, and the rescale is the
    same.  Gradients are those of the exact depthwise conv (straight-
    through).  ``block_m``/``block_d``: output pixels per grid step (an
    upper bound) and the channel block; ``mesh``: the images of ``x``
    are sharded over it.
    """
    if cfg.backend not in ANALOG_CARRY_BACKENDS:
        raise ValueError(
            f"photonic_depthwise: {cfg.backend.value!r} reads every DPE "
            f"chunk through its own ADC, so its depthwise result depends "
            f"on the block-diagonal K layout — run it through "
            f"photonic_matmul")
    if cfg.noise_enabled and key is None:
        raise ValueError(
            "photonic_depthwise: cfg.noise_enabled=True but key=None — "
            "pass a PRNG key, or set noise_enabled=False")
    if impl == "auto":
        impl = "pallas"
    b, h, wd, c = x.shape
    kk = kh * kw
    adc_fs = taom_kernel_mod.calibrated_adc_fs(kk * c, cfg)
    noise = None
    if key is not None and cfg.noise_enabled:
        oh = conv_out_dim(h, kh, stride, padding)
        ow = conv_out_dim(wd, kw, stride, padding)
        noise = sample_noise(key, (b * oh * ow, kk * c), (kk * c, c),
                             cfg).reshape(b, oh, ow, c)
    return _depthwise_ste(x, w, noise, cfg, float(adc_fs), impl,
                          (int(kh), int(kw), int(stride), str(padding)),
                          (int(block_m), int(block_d)), mesh)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
def _ssd_chunked_jax(x, dt, a, b, c, chunk):
    """Differentiable chunked SSD — same decomposition as the kernel but
    with jax.lax.scan across chunks (used on the training path)."""
    bh, l, p = x.shape
    s = b.shape[-1]
    n_chunks = l // chunk
    f32 = jnp.float32
    xc = x.reshape(bh, n_chunks, chunk, p).astype(f32)
    dtc = dt.reshape(bh, n_chunks, chunk).astype(f32)
    bc = b.reshape(bh, n_chunks, chunk, s).astype(f32)
    cc = c.reshape(bh, n_chunks, chunk, s).astype(f32)
    a = a.astype(f32)

    da = dtc * a[:, None, None]                       # (BH, C, Q)
    cum = jnp.cumsum(da, axis=-1)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col

    seg = cum[..., :, None] - cum[..., None, :]       # (BH, C, Q, Q)
    lmat = jnp.where(causal, jnp.exp(seg) * dtc[..., None, :], 0.0)
    scores = jnp.einsum("zkqs,zkts->zkqt", cc, bc) * lmat
    y_intra = jnp.einsum("zkqt,zktp->zkqp", scores, xc)

    # Per-chunk state contribution and decay.
    wgt = jnp.exp(cum[..., -1:] - cum) * dtc          # (BH, C, Q)
    chunk_states = jnp.einsum("zkq,zkqp,zkqs->zkps", wgt, xc, bc)
    chunk_decay = jnp.exp(cum[..., -1])               # (BH, C)

    def step(state, inp):
        cs, cd = inp                                   # (BH,P,S), (BH,)
        new = state * cd[:, None, None] + cs
        return new, state                              # emit state *before*

    init = jnp.zeros((bh, p, s), f32)
    final_state, prev_states = jax.lax.scan(
        step, init, (jnp.moveaxis(chunk_states, 1, 0),
                     jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)      # (BH, C, P, S)

    y_inter = jnp.einsum("zkqs,zkps->zkqp", cc, prev_states) * \
        jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(bh, l, p).astype(x.dtype)
    return y, final_state


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 128,
             impl: str = "auto") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba2 SSD scan (flattened batch*heads layout; see ref.py for shapes).

    Pads L up to a chunk multiple internally.  impl: 'pallas' | 'jax' |
    'auto' ('jax' — differentiable — unless explicitly asked for pallas).
    """
    bh, l, p = x.shape
    lpad = (-l) % chunk
    if lpad:
        x = jnp.pad(x, ((0, 0), (0, lpad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, lpad)))
        b = jnp.pad(b, ((0, 0), (0, lpad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, lpad), (0, 0)))
    if impl == "auto":
        impl = "jax"
    if impl == "pallas":
        y, state = ssd_kernel_mod.ssd_scan_chunked(
            x, dt, a, b, c, chunk=chunk, interpret=_on_cpu())
    else:
        y, state = _ssd_chunked_jax(x, dt, a, b, c, chunk)
    return y[:, :l], state


def ssd_decode_step(state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
                    a: jnp.ndarray, b_t: jnp.ndarray, c_t: jnp.ndarray
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token SSD recurrence for serving.

    state: (BH, P, S); x_t: (BH, P); dt_t: (BH,); a: (BH,);
    b_t, c_t: (BH, S).  Returns (y_t: (BH, P), new_state).
    """
    decay = jnp.exp(dt_t * a)                          # (BH,)
    upd = (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
    new_state = decay[:, None, None] * state + upd
    y = jnp.einsum("zps,zs->zp", new_state, c_t)
    return y.astype(x_t.dtype), new_state
