"""Small-C_in conv route (ops.photonic_conv_small_cin).

A dense conv whose input has at most ``lowering.SMALL_CIN_MAX`` channels
quantizes its padded image once and builds its (M, kh*kw*C) GEMM operand
from the codes with one one-hot convolution, in im2col's K order.  It
gives bitwise what ``im2col`` + ``photonic_matmul`` give, under every
policy (the chunk-ADC policies included, whose reads depend on the K
order), both impls, and noise off or on with the same key.
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
from repro.kernels import ops
from repro.models import lowering as lw
from repro.models.zoo_cnn import ZOO

GEOMETRIES = list(itertools.product((1, 3, 4, 8), (3, 7), (1, 2),
                                    ("same", "valid")))
# HEANA's kernel over every geometry with noise off and on, its oracle
# over a quarter of them; AMW and MAW (per-chunk ADC reads) over both
# impls and noise settings, each at an eighth of the geometries.
GRID = ([("heana", "pallas", noise, g)
         for noise, g in itertools.product((False, True), GEOMETRIES)]
        + [("heana", "ref", noise, g)
           for noise, g in itertools.product((False, True),
                                             GEOMETRIES[1::4])]
        + [(backend, impl, noise, g)
           for bi, backend in enumerate(("amw", "maw"))
           for impl, noise in itertools.product(("ref", "pallas"),
                                                (False, True))
           for g in GEOMETRIES[bi::8]])


def _cfg(backend, noise):
    op = hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0,
                                      noise_enabled=noise)
    return op.kernel_config()


def _im2col_gemm(x, w, cfg, key, impl, kk, stride, padding):
    cols, (oh, ow) = lw.im2col(x, kk, kk, stride, padding)
    out = ops.photonic_matmul(cols.reshape(-1, cols.shape[-1]), w, cfg,
                              key=key, impl=impl)
    return out.reshape(x.shape[0], oh, ow, w.shape[-1])


@pytest.mark.parametrize(
    "backend,impl,noise,c,kk,stride,padding",
    [g[:3] + g[3] for g in GRID],
    ids=lambda v: str(v))
def test_small_cin_equals_im2col_gemm(backend, impl, noise, c, kk, stride,
                                      padding):
    cfg = _cfg(backend, noise)
    kx, kw_ = jax.random.split(jax.random.PRNGKey(c + 10 * kk + stride))
    x = jax.random.normal(kx, (2, 11, 9, c))
    w = jax.random.normal(kw_, (kk * kk * c, 12))
    key = jax.random.PRNGKey(5) if noise else None
    want = jax.jit(lambda x, w, k: _im2col_gemm(
        x, w, cfg, k, impl, kk, stride, padding))(x, w, key)
    got = jax.jit(lambda x, w, k: ops.photonic_conv_small_cin(
        x, w, cfg, key=k, impl=impl, kh=kk, kw=kk, stride=stride,
        padding=padding, block_m=16))(x, w, key)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_small_cin_equals_im2col_gemm_eagerly(impl):
    """Op by op, outside jit: ResNet-50's conv1 geometry, 7x7/2 SAME on
    three channels."""
    cfg = _cfg("heana", True)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 20, 18, 3))
    w = jax.random.normal(jax.random.PRNGKey(4), (147, 16))
    key = jax.random.PRNGKey(8)
    want = _im2col_gemm(x, w, cfg, key, impl, 7, 2, "same")
    got = ops.photonic_conv_small_cin(x, w, cfg, key=key, impl=impl, kh=7,
                                      kw=7, stride=2, padding="same")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_small_cin_gradients_are_the_exact_conv():
    cfg = _cfg("heana", False)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 9, 3))
    w = jax.random.normal(jax.random.PRNGKey(5), (27, 8))

    def photonic(x, w):
        return ops.photonic_conv_small_cin(x, w, cfg, impl="ref", kh=3,
                                           kw=3, stride=2)

    def exact(x, w):
        return jax.lax.conv_general_dilated(
            x, w.reshape(3, 3, 3, 8), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    g = 2.0 * photonic(x, w)
    want = jax.vjp(exact, x, w)[1](g)
    got = jax.grad(lambda x, w: jnp.sum(photonic(x, w) ** 2),
                   argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_exact_backend_runs_the_exact_conv():
    exact_cfg = hw.OperatingPoint.equal_area(
        "heana", Dataflow.OS, 1.0).kernel_config(backend=Backend.EXACT)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 3))
    w = jax.random.normal(jax.random.PRNGKey(2), (27, 4))
    got = ops.photonic_conv_small_cin(x, w, exact_cfg, kh=3, kw=3)
    want = jax.lax.conv_general_dilated(
        x, w.reshape(3, 3, 3, 4), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_small_cin_refuses_noise_without_a_key():
    with pytest.raises(ValueError, match="key=None"):
        ops.photonic_conv_small_cin(jnp.zeros((1, 4, 4, 3)),
                                    jnp.zeros((27, 4)), _cfg("heana", True),
                                    kh=3, kw=3)


def _parent_forward(params, xs, model, plan, cfg):
    """The executor's program with every conv on its im2col GEMM."""
    def mm(a, w, gi, node):
        return ex._layer_matmul(a, w, cfg, None, plan.layers[gi], "pallas",
                                None)

    def dw(a, w, gi, node):
        return ex._layer_depthwise(a, w, cfg, None, plan.layers[gi], node,
                                   "pallas", None)

    grouped = lw.grouped_depthwise(cfg.backend)
    vals = lw.graph_forward(params, xs, model.graph, mm,
                            dw if grouped else None)
    return vals[model.graph.output.name]


@pytest.mark.parametrize("batch", [1, 8, 32])
@pytest.mark.parametrize("name", ["resnet_mini", "mobilenet_mini"])
def test_forward_fn_equals_the_im2col_program(name, batch):
    """Through ``forward_fn`` at serving buckets: the logits of the
    compiled executor equal those of the same program with its stems on
    ``im2col``."""
    model = ZOO[name]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    cfg = op.kernel_config()
    params = model.init_params(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(batch),
                           (batch, *model.in_hw, model.in_ch))
    plan = ex.plan_for_network(params, op, batch=batch, in_hw=model.in_hw,
                               lowering=model.graph, cache=PlanCache())
    got = execute_cnn(params, xs, plan, cfg, impl="pallas",
                      lowering=model.graph).logits
    want = jax.jit(lambda p, x: _parent_forward(p, x, model, plan, cfg))(
        params, xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cin,routed", [(1, True), (3, True), (8, True),
                                        (9, False), (16, False)])
def test_the_rule_engages_for_at_most_eight_channels(cin, routed):
    node = lw.conv("c", "input", 4)
    assert lw.small_cin_conv(node, cin) is routed
    assert not lw.small_cin_conv(lw.dwconv("d", "input"), cin)
    graph = lw.OpGraph((lw.input_node(cin), node, lw.global_avg("g", "c"),
                        lw.fc("fc", "g", 2)))
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    params = lw.init_params(graph, jax.random.PRNGKey(0), 6)
    plan = ex.plan_for_network(params, op, batch=1, in_hw=6,
                               lowering=graph, cache=PlanCache())
    xs = jnp.zeros((1, 6, 6, cin))
    jaxpr = str(jax.make_jaxpr(lambda p, x: ex._forward(
        p, x, None, lowering=graph, plan=plan, cfg=op.kernel_config(),
        impl="ref", collect_activations=False))(params, xs))
    assert ("conv_general_dilated" in jaxpr) is routed
    assert ex.small_cin_rows(plan, graph) == (36 if routed else 0)


def test_small_cin_rows_total_counts_the_dispatched_buckets():
    model = ZOO["mobilenet_mini"]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(params, op, lowering=model.graph, in_hw=model.in_hw,
                        max_batch=4, impl="ref")
    shapes = lw.infer_shapes(model.graph, model.in_hw)
    # Per image: the output pixels of every conv fed by at most 8
    # channels (the stem, and ir1_pw behind the 8-channel depthwise).
    routed = [n for n in model.graph.gemm_nodes
              if n.op == "conv" and shapes[n.inputs[0]][2] <= 8]
    assert [n.name for n in routed] == ["stem", "ir1_pw"]
    rows = sum(shapes[n.name][0] * shapes[n.name][1] for n in routed)
    assert ex.small_cin_rows(eng.plans[4], model.graph) == 4 * rows
    x = jnp.zeros((4, *model.in_hw, model.in_ch))
    for n in (4, 2, 1):             # buckets 4, 2, 1
        eng.infer(x[:n])
    stats = eng.stats()
    assert stats["images"] == stats["executed_slots"] == 7
    assert stats["small_cin_rows_total"] == 7 * rows
    eng.infer(x[:3])                # bucket 4: a padding image included
    assert eng.stats()["small_cin_rows_total"] == 11 * rows
