"""Compiles of the TAOM kernel for a described TPU v5e (no chip needed).

Interpret mode on the CPU never sees what the chip's compiler refuses:
tiles that overflow VMEM, unaligned blocks.  These tests compile the
kernel with the TPU compiler, for a v5e that is described and not
attached, at the largest tiles ``exec.scheduler.choose_tile`` admits, and
check on the host that the scheduler never hands out a tile over the
kernel's VMEM budget.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hw
from repro.core.types import Backend, Dataflow
from repro.exec.scheduler import choose_depthwise_tile, choose_tile
from repro.kernels import taom_depthwise as tdw
from repro.kernels.taom_gemm import (VMEM_BUDGET_BYTES, taom_gemm_quantized,
                                     vmem_bytes)
from repro.models.lowering import LayerGemm, grouped_depthwise
from repro.models.zoo_cnn import PAPER_ZOO

BACKENDS = ("heana", "amw", "maw")
# (M, K, D) with K spanning many DPE chunks: mobilenet_mini's ir3_dw at
# batch 256 (block-diagonal depthwise, D=144) and a batch-256 3x3 conv
# shape with 256 output channels.
BIG_GEMMS = ((16384, 1296, 144), (65536, 576, 256))
# MobileNetV2's depthwise layers at 32 images per call, (B, H, W, C, stride)
# of the layer's input: the widest (mb16_0_dw), one at 56x56 with C=144
# (mb24_1_dw) and the deepest (mb160_1_dw).
MOBILENET_V2_B32_DW = {"mb16_0_dw": (32, 112, 112, 32, 1),
                       "mb24_1_dw": (32, 56, 56, 144, 1),
                       "mb160_1_dw": (32, 7, 7, 960, 1)}


@pytest.fixture(scope="module")
def one_chip():
    """A v5e core to compile for, with the persistent compile cache off
    (an entry written here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.mark.parametrize("mkd", BIG_GEMMS, ids=lambda g: "x".join(map(str, g)))
@pytest.mark.parametrize("backend", ("heana", "amw"))
def test_kernel_compiles_at_largest_admitted_tile(one_chip, backend, mkd):
    m, k, d = mkd
    cfg = hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0,
                                       noise_enabled=False).kernel_config()
    tile = choose_tile(m, d, k, cfg.dpe_size)
    assert vmem_bytes(tile.block_m, tile.block_d,
                      cfg.dpe_size) <= VMEM_BUDGET_BYTES
    chunks = -(-k // cfg.dpe_size)
    noise = ((chunks, m, d) if cfg.backend in (Backend.AMW, Backend.MAW)
             else (m, d))

    def shape(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    fn = jax.jit(lambda x, w, z: taom_gemm_quantized(
        x, w, z, cfg, 100.0, block_m=tile.block_m, block_d=tile.block_d))
    compiled = fn.lower(shape((m, k)), shape((k, d)), shape(noise)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # Each accumulation policy's kernel keeps a stable name in the trace.
    name = "taom_chunk_adc" if backend == "amw" else "taom_analog_carry"
    assert f"%{name}." in text


@pytest.mark.parametrize("backend", BACKENDS)
def test_scheduler_tiles_fit_vmem_budget(backend):
    """Host only: every GEMM the TAOM GEMM kernel runs for the four
    minis, batches 1-256 (grouped depthwise layers run the depthwise
    kernel, whose steps tests/test_depthwise.py fits)."""
    n = hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0).n
    for model in PAPER_ZOO.values():
        for g in model.gemms():
            if g.depthwise and grouped_depthwise(backend):
                continue
            for batch in range(1, 257):
                m, k, d = LayerGemm(g.name, g.c * batch, g.k, g.d,
                                    g.count).executed(backend)
                t = choose_tile(m, d, k, n)
                assert vmem_bytes(t.block_m, t.block_d,
                                  n) <= VMEM_BUDGET_BYTES, (model.name,
                                                            g.name, batch, t)


@pytest.mark.parametrize("layer", sorted(MOBILENET_V2_B32_DW))
def test_depthwise_kernel_compiles_at_mobilenet_v2_b32(one_chip, layer):
    """``taom_depthwise`` at the tile the scheduler plans, noise off (no
    noise operand) and on."""
    b, h, w, c, s = MOBILENET_V2_B32_DW[layer]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    cfg = op.kernel_config()
    tile = choose_depthwise_tile(b * h * w, c, 9, cfg.dpe_size)
    hp, wp = h + 2, w + 2           # 'same' 3x3 at stride 1

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    for noise in (None, shape(b, h, w, c)):
        fn = jax.jit(lambda x, wq, z: tdw.taom_depthwise_quantized(
            x, wq, z, cfg=cfg, adc_fs=100.0, kh=3, kw=3, stride=s,
            block_m=tile.block_m, block_d=tile.block_d))
        text = fn.lower(shape(b, hp, wp, c), shape(9, c),
                        noise).compile().as_text()
        assert "%taom_depthwise." in text
        assert "taom_analog_carry" not in text
