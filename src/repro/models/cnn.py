"""The paper's CNN workloads as im2col GEMM tables (+ a runnable small CNN).

The paper's simulator consumes convolution layers as GEMMs via the Toeplitz
/ im2col transform (paper §2.1): a conv with C_in input channels, k x k
kernel, C_out filters and H_out x W_out output pixels becomes

    I (C x K) @ W (K x D)   with  C = H_out * W_out,
                                  K = C_in * k * k,
                                  D = C_out.

Depthwise convolutions are grouped GEMMs: ``count`` instances of a
(C, k*k, 1) GEMM.  All four evaluation CNNs (GoogleNet, ResNet50,
MobileNetV2, ShuffleNetV2 — paper §6.2) are generated below from their
published block structures at 224x224 input.

``build_small_cnn``/``small_cnn_apply`` additionally provide a *runnable*
(forward-pass) CNN used by the Table 4 accuracy experiments, whose conv
layers execute through the photonic GEMM simulation.

Runnable networks are described by the op-graph IR (models.lowering.
OpGraph): stride/padding convs, depthwise convs, pooling, residual adds,
concats, channel shuffles.  ``small_cnn_graph`` is the small CNN on it;
the paper's four evaluation networks have reduced-scale runnable
variants in models.zoo_cnn.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.models import lowering as lw
# Re-exported: LayerGemm's home is the lowering IR now (single source of
# truth for analytic tables AND runnable graphs), but every historical
# importer uses models.cnn.LayerGemm.
from repro.models.lowering import LayerGemm, OpGraph  # noqa: F401


def _conv(name, hw, cin, kk, cout, count=1) -> LayerGemm:
    return LayerGemm(name, hw * hw, cin * kk * kk, cout, count)


def _dw(name, hw, ch, kk=3) -> LayerGemm:
    # depthwise: per-channel (C, kk*kk, 1) GEMMs
    return LayerGemm(name, hw * hw, kk * kk, 1, count=ch)


def googlenet() -> List[LayerGemm]:
    L: List[LayerGemm] = [
        _conv("conv1", 112, 3, 7, 64),
        _conv("conv2_reduce", 56, 64, 1, 64),
        _conv("conv2", 56, 64, 3, 192),
    ]
    # (hw, c_in, 1x1, r3, 3x3, r5, 5x5, pool_proj)
    inception = [
        ("3a", 28, 192, 64, 96, 128, 16, 32, 32),
        ("3b", 28, 256, 128, 128, 192, 32, 96, 64),
        ("4a", 14, 480, 192, 96, 208, 16, 48, 64),
        ("4b", 14, 512, 160, 112, 224, 24, 64, 64),
        ("4c", 14, 512, 128, 128, 256, 24, 64, 64),
        ("4d", 14, 512, 112, 144, 288, 32, 64, 64),
        ("4e", 14, 528, 256, 160, 320, 32, 128, 128),
        ("5a", 7, 832, 256, 160, 320, 32, 128, 128),
        ("5b", 7, 832, 384, 192, 384, 48, 128, 128),
    ]
    for tag, hw, cin, b1, r3, b3, r5, b5, pp in inception:
        L += [
            _conv(f"inc{tag}_1x1", hw, cin, 1, b1),
            _conv(f"inc{tag}_3x3r", hw, cin, 1, r3),
            _conv(f"inc{tag}_3x3", hw, r3, 3, b3),
            _conv(f"inc{tag}_5x5r", hw, cin, 1, r5),
            _conv(f"inc{tag}_5x5", hw, r5, 5, b5),
            _conv(f"inc{tag}_pool", hw, cin, 1, pp),
        ]
    L.append(LayerGemm("fc", 1, 1024, 1000))
    return L


def googlenet_layer5() -> LayerGemm:
    """'Layer 5 of GoogleNet' used by the paper's Fig. 1 buffer-access table
    (5th conv layer = the inception-3a 3x3 branch)."""
    return next(l for l in googlenet() if l.name == "inc3a_3x3")


def resnet50() -> List[LayerGemm]:
    L = [_conv("conv1", 112, 3, 7, 64)]
    stages = [  # (hw_out, c_in_first, width, c_out, blocks)
        (56, 64, 64, 256, 3),
        (28, 256, 128, 512, 4),
        (14, 512, 256, 1024, 6),
        (7, 1024, 512, 2048, 3),
    ]
    for hw, cin_first, wdt, cout, blocks in stages:
        for bi in range(blocks):
            cin = cin_first if bi == 0 else cout
            tag = f"s{hw}b{bi}"
            L += [
                _conv(f"{tag}_1x1a", hw, cin, 1, wdt),
                _conv(f"{tag}_3x3", hw, wdt, 3, wdt),
                _conv(f"{tag}_1x1b", hw, wdt, 1, cout),
            ]
            if bi == 0:
                L.append(_conv(f"{tag}_ds", hw, cin, 1, cout))
    L.append(LayerGemm("fc", 1, 2048, 1000))
    return L


def mobilenet_v2() -> List[LayerGemm]:
    L = [_conv("conv1", 112, 3, 3, 32)]
    # (expansion t, c_out, repeats n, hw_out_of_first_block)
    cfg = [(1, 16, 1, 112), (6, 24, 2, 56), (6, 32, 3, 28), (6, 64, 4, 14),
           (6, 96, 3, 14), (6, 160, 3, 7), (6, 320, 1, 7)]
    cin, hw_in = 32, 112
    for t, cout, n, hw_out in cfg:
        for bi in range(n):
            hw = hw_out if bi == 0 else hw_out
            hidden = cin * t
            tag = f"mb{cout}_{bi}"
            if t > 1:
                L.append(_conv(f"{tag}_expand", hw_in if bi == 0 else hw,
                               cin, 1, hidden))
            L.append(_dw(f"{tag}_dw", hw, hidden))
            L.append(_conv(f"{tag}_project", hw, hidden, 1, cout))
            cin, hw_in = cout, hw
    L.append(_conv("conv_last", 7, 320, 1, 1280))
    L.append(LayerGemm("fc", 1, 1280, 1000))
    return L


def shufflenet_v2() -> List[LayerGemm]:
    L = [_conv("conv1", 112, 3, 3, 24)]
    stages = [  # (hw_out, c_in, c_branch, blocks)
        (28, 24, 58, 4),
        (14, 116, 116, 8),
        (7, 232, 232, 4),
    ]
    for hw, cin, cb, blocks in stages:
        hw_in = hw * 2
        # stride-2 block: two branches
        L += [
            _dw(f"sh{hw}s2_b1dw", hw, cin),
            _conv(f"sh{hw}s2_b1pw", hw, cin, 1, cb),
            _conv(f"sh{hw}s2_b2pw1", hw_in, cin, 1, cb),
            _dw(f"sh{hw}s2_b2dw", hw, cb),
            _conv(f"sh{hw}s2_b2pw2", hw, cb, 1, cb),
        ]
        for bi in range(1, blocks):
            L += [
                _conv(f"sh{hw}b{bi}_pw1", hw, cb, 1, cb),
                _dw(f"sh{hw}b{bi}_dw", hw, cb),
                _conv(f"sh{hw}b{bi}_pw2", hw, cb, 1, cb),
            ]
    L.append(_conv("conv5", 7, 464, 1, 1024))
    L.append(LayerGemm("fc", 1, 1024, 1000))
    return L


CNN_ZOO: Dict[str, Callable[[], List[LayerGemm]]] = {
    "googlenet": googlenet,
    "resnet50": resnet50,
    "mobilenet_v2": mobilenet_v2,
    "shufflenet_v2": shufflenet_v2,
}


def total_macs(layers: List[LayerGemm]) -> int:
    return sum(l.macs for l in layers)


# ---------------------------------------------------------------------------
# GEMM lowering hooks (consumed by repro.exec — the execution engine)
# ---------------------------------------------------------------------------
def small_cnn_graph(num_classes: int = 10, in_ch: int = 3) -> OpGraph:
    """build_small_cnn as op-graph IR: conv-relu-pool, conv-relu-pool,
    conv-relu, fc."""
    return OpGraph((
        lw.input_node(in_ch),
        lw.conv("conv1", "input", 16),
        lw.pool("conv1.pool", "conv1"),
        lw.conv("conv2", "conv1.pool", 32),
        lw.pool("conv2.pool", "conv2"),
        lw.conv("conv3", "conv2.pool", 32),
        lw.fc("fc", "conv3", num_classes),
    ))


def lowered_gemms(params: dict, graph: Optional[OpGraph] = None,
                  in_hw=16) -> List[LayerGemm]:
    """Analytic GEMM table (for the scheduler) of a lowered runnable CNN.

    Walks the op graph (default: the small CNN), tracking spatial size
    through strides and pools, validating every weight shape against the
    graph — the same (C, K, D) the executor will feed the kernel, so
    plans and execution agree.

    ``in_hw`` is the input spatial size: an int for square images or an
    (H, W) pair for rectangular ones (conv rows become H_out*W_out).
    """
    return lw.graph_gemms(graph or small_cnn_graph(), in_hw, params=params)


# ---------------------------------------------------------------------------
# Runnable small CNN for the accuracy (Table 4) experiments
# ---------------------------------------------------------------------------
def build_small_cnn(key: jax.Array, num_classes: int = 10,
                    in_hw: int = 16, in_ch: int = 3) -> dict:
    """A small conv net (3 conv + 1 fc) with explicit im2col GEMM layers."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def glorot(k, shape):
        fan_in = shape[0]
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)

    return {
        "conv1": glorot(k1, (in_ch * 9, 16)),
        "conv2": glorot(k2, (16 * 9, 32)),
        "conv3": glorot(k3, (32 * 9, 32)),
        "fc": glorot(k4, ((in_hw // 4) ** 2 * 32, num_classes)),
    }


def lowered_apply(params: dict, x: jnp.ndarray,
                  graph: Optional[OpGraph] = None,
                  matmul: Optional[Callable] = None) -> jnp.ndarray:
    """Forward pass of ANY lowered runnable CNN, driven by its op graph
    (default: the small CNN).

    The single source of truth for what a graph computes: the executor
    (repro.exec.executor) replays exactly this structure through the
    Pallas kernel, and the bit-exactness oracle (exec.executor.
    reference_forward) calls this with the *same* graph the executor
    ran — so the contract covers every lowered network (stride/
    depthwise/residual/pool included), not just the small CNN.

    ``matmul(a, w)`` defaults to exact and can be the photonic simulation
    (ops.photonic_matmul partial).  Rectangular images are first-class.
    """
    return lw.graph_apply(params, x, graph or small_cnn_graph(), matmul)


def small_cnn_apply(params: dict, x: jnp.ndarray,
                    matmul: Optional[Callable] = None) -> jnp.ndarray:
    """Forward pass of the small CNN; delegates to ``lowered_apply`` with
    its own graph so forward and lowering cannot drift."""
    return lowered_apply(params, x, small_cnn_graph(), matmul)
