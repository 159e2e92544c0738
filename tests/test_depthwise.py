"""Grouped depthwise path (kernels/taom_depthwise.py, ops.photonic_depthwise).

Under an analog-carry policy (HEANA, ``*_bpca``) a depthwise layer runs
as kh*kw shifted tap reads, and gives bitwise what its block-diagonal
(kh*kw*C, C) GEMM gives through ``photonic_matmul``: the same codes,
exact integer sums, the same noise draws for the same key, one ADC read
at the same full scale and the same rescale.  Chunk-ADC policies (AMW,
MAW) keep the block-diagonal GEMM.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hw
from repro.core.types import Backend, Dataflow
from repro.exec import PlanCache, ServingEngine, execute_cnn
from repro.exec import executor as ex
from repro.exec.scheduler import choose_depthwise_tile, schedule_cnn
from repro.kernels import ops
from repro.kernels import taom_depthwise as tdw
from repro.kernels.taom_gemm import VMEM_BUDGET_BYTES
from repro.models import lowering as lw
from repro.models.zoo_cnn import ZOO

ANALOG_CARRY = ("heana", "amw_bpca", "maw_bpca")
CHUNK_ADC = ("amw", "maw")


def _cfg(backend, noise):
    base = backend.replace("_bpca", "")
    op = hw.OperatingPoint.equal_area(base, Dataflow.OS, 1.0,
                                      noise_enabled=noise)
    return op.kernel_config(backend=Backend(backend))


def _block_diagonal(x, w, cfg, key, impl, stride, padding, kh=3, kw=3):
    cols, (oh, ow) = lw.im2col(x, kh, kw, stride, padding)
    out = ops.photonic_matmul(cols.reshape(-1, cols.shape[-1]),
                              lw.depthwise_block_diag(w), cfg, key=key,
                              impl=impl)
    return out.reshape(x.shape[0], oh, ow, w.shape[-1])


# HEANA over every combination; the *_bpca policies (other N, so other
# chunk counts and noise scales) over both impls and noise settings at
# two geometries.
GRID = (list(itertools.product(("heana",), ("ref", "pallas"), (False, True),
                               (1, 2), ("same", "valid"), (8, 83, 96, 200)))
        + list(itertools.product(("amw_bpca", "maw_bpca"), ("ref", "pallas"),
                                 (False, True), ((1, "same", 83),
                                                 (2, "valid", 200)))))


@pytest.mark.parametrize(
    "backend,impl,noise,stride,padding,c",
    [g if len(g) == 6 else g[:3] + g[3] for g in GRID])
def test_grouped_equals_block_diagonal(backend, impl, noise, stride,
                                       padding, c):
    cfg = _cfg(backend, noise)
    kx, kw_ = jax.random.split(jax.random.PRNGKey(c + 7 * stride))
    x = jax.random.normal(kx, (2, 7, 6, c))
    w = jax.random.normal(kw_, (9, c))
    key = jax.random.PRNGKey(11) if noise else None
    want = jax.jit(lambda x, w, k: _block_diagonal(
        x, w, cfg, k, impl, stride, padding))(x, w, key)
    # A small pixel block: several row tiles, each with its halo.
    got = jax.jit(lambda x, w, k: ops.photonic_depthwise(
        x, w, cfg, key=k, impl=impl, kh=3, kw=3, stride=stride,
        padding=padding, block_m=12))(x, w, key)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_grouped_equals_block_diagonal_eagerly(impl):
    """Op by op, outside jit."""
    cfg = _cfg("heana", True)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 9, 10, 24))
    w = jax.random.normal(jax.random.PRNGKey(13), (9, 24))
    key = jax.random.PRNGKey(14)
    got = ops.photonic_depthwise(x, w, cfg, key=key, impl=impl, kh=3, kw=3,
                                 stride=2, block_m=16)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(_block_diagonal(x, w, cfg, key, impl, 2, "same")))


@pytest.mark.parametrize("noise", [False, True])
def test_grouped_equals_block_diagonal_under_jit(noise):
    """One jitted program each, with whole images per grid step."""
    cfg = _cfg("heana", noise)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 12, 40))
    w = jax.random.normal(jax.random.PRNGKey(2), (9, 40))
    key = jax.random.PRNGKey(3) if noise else None
    for stride in (1, 2):
        grouped = jax.jit(lambda x, w, k: ops.photonic_depthwise(
            x, w, cfg, key=k, kh=3, kw=3, stride=stride))
        block = jax.jit(lambda x, w, k: _block_diagonal(
            x, w, cfg, k, "pallas", stride, "same"))
        np.testing.assert_array_equal(np.asarray(grouped(x, w, key)),
                                      np.asarray(block(x, w, key)))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_grouped_equals_block_diagonal_when_stride_exceeds_window(impl):
    """A 2x2 window at stride 3 skips pixels: the activation scale is the
    max over the sampled ones only, as the im2col's."""
    cfg = _cfg("heana", False)
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 11, 8, 16))
    x = x.at[:, 2].set(50.0)        # a row no window reads
    w = jax.random.normal(jax.random.PRNGKey(16), (4, 16))
    got = ops.photonic_depthwise(x, w, cfg, impl=impl, kh=2, kw=2,
                                 stride=3, padding="valid")
    want = _block_diagonal(x, w, cfg, None, impl, 3, "valid", kh=2, kw=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_gradients_are_the_exact_depthwise_convs():
    cfg = _cfg("heana", False)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 16))
    w = jax.random.normal(jax.random.PRNGKey(5), (9, 16))

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) ** 2)

    photonic = loss(lambda x, w: ops.photonic_depthwise(
        x, w, cfg, impl="ref", kh=3, kw=3, stride=2))
    y = ops.photonic_depthwise(x, w, cfg, impl="ref", kh=3, kw=3, stride=2)
    g = 2.0 * y

    def exact(x, w):
        return jax.lax.conv_general_dilated(
            x, w.reshape(3, 3, 1, 16), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=16)

    want = jax.vjp(exact, x, w)[1](g)
    got = jax.grad(photonic, argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("backend", CHUNK_ADC)
def test_chunk_adc_policies_keep_the_block_diagonal_gemm(backend):
    """AMW/MAW: the wrapper refuses them, and the executor runs their
    depthwise layers as the block-diagonal GEMM it ran before."""
    cfg = _cfg(backend, False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 6, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (9, 8))
    with pytest.raises(ValueError, match="block-diagonal"):
        ops.photonic_depthwise(x, w, cfg, kh=3, kw=3)

    model = ZOO["mobilenet_mini"]
    op = hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0,
                                      noise_enabled=False)
    params = model.init_params(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(2), (2, *model.in_hw,
                                                   model.in_ch))
    plan = ex.plan_for_network(params, op, batch=2, in_hw=model.in_hw,
                               lowering=model.graph, cache=PlanCache())
    got = execute_cnn(params, xs, plan, cfg, impl="ref",
                      lowering=model.graph).logits

    @jax.jit
    def block_diagonal(params, xs):
        def mm(a, w, gi, node):
            return ops.photonic_matmul(a, w, cfg, impl="ref")
        vals = lw.graph_forward(params, xs, model.graph, mm)
        return vals[model.graph.output.name]

    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(block_diagonal(params, xs)))
    jaxpr = str(jax.make_jaxpr(lambda p, x: ex._forward(
        p, x, None, lowering=model.graph, plan=plan, cfg=cfg,
        impl="pallas", collect_activations=False))(params, xs))
    assert "taom_depthwise" not in jaxpr and "taom_chunk_adc" in jaxpr


def _engine(op, params, model, max_batch=4, impl="auto"):
    return ServingEngine(params, op, lowering=model.graph, in_hw=model.in_hw,
                         max_batch=max_batch, impl=impl)


@pytest.mark.parametrize("noise", [False, True])
def test_serving_grouped_equals_forced_block_diagonal(noise, monkeypatch):
    """mobilenet_mini on HEANA through ServingEngine: the grouped route
    gives the logits of the same engine with depthwise layers forced
    onto the block-diagonal GEMM (plans and programs of their own)."""
    model = ZOO["mobilenet_mini"]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=noise)
    params = model.init_params(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(6), (3, *model.in_hw,
                                                   model.in_ch))
    key = jax.random.PRNGKey(9) if noise else None
    grouped = _engine(op, params, model)
    want_dw = [p for p in grouped.plans[4].layers if p.count > 1]
    assert want_dw and all(p.tile.block_d == tdw.choose_block_c(p.count)
                           for p in want_dw)
    got = np.asarray(grouped.infer(xs, key=key))

    monkeypatch.setattr(lw, "grouped_depthwise", lambda backend: False)
    forced = _engine(op, params, model)
    keys = {p.cache_key for p in grouped.plans[4].layers}
    assert {p.cache_key for p in forced.plans[4].layers} != keys
    want = np.asarray(forced.infer(xs, key=key))
    np.testing.assert_array_equal(got, want)


def test_executed_macs_total_counts_the_dispatched_programs():
    model = ZOO["mobilenet_mini"]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(op, params, model, impl="ref")
    per_image, dw_per_image = ex.executed_macs(eng.plans[1])
    assert ex.executed_macs(eng.plans[4]) == (4 * per_image,
                                              4 * dw_per_image)
    x = jnp.zeros((4, *model.in_hw, model.in_ch))
    for n in (4, 4, 3, 1):          # buckets 4, 4, 4, 1
        eng.infer(x[:n])
    stats = eng.stats()
    assert stats["executed_macs_total"] == 13 * per_image
    assert stats["depthwise_executed_macs_total"] == 13 * dw_per_image
    # Grouped depthwise layers perform their logical work, no more.
    dw = sum(g.macs for g in lw.graph_gemms(model.graph, model.in_hw)
             if g.depthwise)
    assert dw_per_image == dw


@pytest.mark.parametrize("backend", ["heana", "amw"])
def test_executed_macs_lay_k_out_in_chunk_slots(backend):
    op = hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0)
    gemms = [lw.LayerGemm("conv", 10, 100, 20),
             lw.LayerGemm("dw", 10, 9, 1, count=30)]
    plan = schedule_cnn(gemms, op, batch=2, cache=PlanCache())
    total, dw = ex.executed_macs(plan)
    slot = 128
    conv = 20 * -(-100 // op.n) * slot * 20
    if backend == "heana":
        assert dw == 20 * 9 * 30
    else:
        assert dw == 20 * -(-270 // op.n) * slot * 30
    assert total == conv + dw


MOBILENET_V2_DW = [(112, 32, 1), (112, 96, 2), (56, 144, 1), (56, 144, 2),
                   (28, 192, 1), (28, 192, 2), (14, 384, 1), (14, 576, 1),
                   (14, 576, 2), (7, 960, 1)]


@pytest.mark.parametrize("hw_in,c,stride", MOBILENET_V2_DW)
def test_planned_depthwise_steps_fit_vmem(hw_in, c, stride):
    """Host only: at every batch up to 64, the step the kernel takes
    for the scheduler's tile fits its VMEM budget, and its rows cover
    the output."""
    oh = -(-hw_in // stride)
    for batch in range(1, 65):
        t = choose_depthwise_tile(batch * oh * oh, c, 9, 83)
        bc = tdw.block_c(c, t.block_d, stride)
        for noise in (False, True):
            nb, th = tdw.step_shape(t.block_m, batch, oh, oh, bc, 3, 3,
                                    stride, noise)
            assert batch % nb == 0 and 1 <= th <= oh
            assert tdw.vmem_bytes(nb, th, oh, bc, 3, 3, stride,
                                  noise) <= VMEM_BUDGET_BYTES


def test_layer_gemm_executed_follows_the_policy():
    g = lw.LayerGemm("dw", 49, 9, 1, count=960)
    for backend in ANALOG_CARRY:
        assert g.executed(backend) == (49, 9, 960)
        assert g.executed(Backend(backend)) == (49, 9, 960)
    for backend in CHUNK_ADC:
        assert g.executed(backend) == (49, 9 * 960, 960)
    conv = lw.LayerGemm("conv", 49, 160, 960)
    assert conv.executed("heana") == conv.executed("amw") == (49, 160, 960)
