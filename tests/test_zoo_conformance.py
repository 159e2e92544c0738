"""Differential conformance suite for the executable model zoo (ISSUE 3).

For EVERY zoo network (the four paper-CNN reduced variants + the small
CNN):

  * the compiled Pallas executor output is BIT-EXACT vs the pure-jnp
    oracle (kernels/ref.py) under the same compilation — the executor's
    jitted forward with the reference GEMM in place of the kernel — with
    noise off;
  * warm compiled calls never retrace (trace_count pins it per model);
  * the runnable graph's GEMM table equals the paper-style analytic
    accounting (models.cnn._conv/_dw formulas — what feeds
    benchmarks/fig11_fps.py) layer by layer, so modeled MACs and
    executed MACs come from one source of truth;
  * golden-trace regression: per-layer fingerprints for a fixed seed on
    resnet_mini are checked in — a kernel/scheduler refactor that
    silently changes numerics fails loudly.

Plus the explicit spatial-validation contract (the old spatial-dims/
pooling code assumed even square dims and failed with reshape noise).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, execute_cnn, graph_summary,
                        plan_for_network, trace_count)
from repro.models import cnn, lowering as lw
from repro.models.zoo_cnn import PAPER_ZOO, ZOO

HEANA = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _cfg(noise=False):
    # bits=6 keeps every partial sum < 2^24 — exact float accumulation,
    # the precondition of the bit-exactness contract (see test_exec).
    return PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                          noise_enabled=noise)


def _setup(model, batch=2, seed=0, acc=HEANA):
    params = model.init_params(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                          (batch, *model.in_hw, model.in_ch))
    plan = plan_for_network(params, acc, batch=batch, in_hw=model.in_hw,
                            lowering=model.graph, cache=PlanCache())
    return params, x, plan


class TestZooConformance:
    """Acceptance: all four paper-CNN reduced variants execute end-to-end
    through the compiled path, bit-exact vs the reference oracle."""

    @pytest.mark.parametrize("name", list(ZOO))
    def test_compiled_pallas_bit_exact_vs_oracle(self, name):
        """Kernel == oracle under the same compilation (jit vs jit): the
        same jitted program with only the GEMM swapped.  A separately
        jitted reference_forward is another program, and XLA may fuse
        its glue differently (googlenet_mini's global-average reduce
        drifts by 1 ULP on the CPU backend)."""
        model = ZOO[name]
        params, x, plan = _setup(model)
        res = execute_cnn(params, x, plan, _cfg(), impl="pallas",
                          lowering=model.graph)
        ref = execute_cnn(params, x, plan, _cfg(), impl="ref",
                          lowering=model.graph)
        np.testing.assert_array_equal(np.asarray(res.logits),
                                      np.asarray(ref.logits))
        assert res.logits.shape == (2, model.num_classes)

    @pytest.mark.parametrize("name", list(ZOO))
    def test_zero_warm_retraces(self, name):
        model = ZOO[name]
        params, x, plan = _setup(model)
        execute_cnn(params, x, plan, _cfg(), lowering=model.graph)  # cold
        before = trace_count()
        for _ in range(3):
            execute_cnn(params, x, plan, _cfg(), lowering=model.graph)
        assert trace_count() == before
        # an equal replanned plan must hit the same executable
        plan2 = plan_for_network(params, HEANA, batch=2,
                                 in_hw=model.in_hw, lowering=model.graph,
                                 cache=PlanCache())
        execute_cnn(params, x, plan2, _cfg(), lowering=model.graph)
        assert trace_count() == before

    @pytest.mark.parametrize("name", list(ZOO))
    def test_lowered_matches_direct_conv_reference(self, name):
        """The im2col/block-diagonal lowering == jax.lax.conv numerics
        (exact matmul, no photonic pipeline)."""
        model = ZOO[name]
        params = model.init_params(jax.random.PRNGKey(3))
        x = jax.random.normal(jax.random.PRNGKey(4),
                              (2, *model.in_hw, model.in_ch))
        got = lw.graph_apply(params, x, model.graph)
        want = lw.direct_forward(params, x, model.graph)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-6)

    @pytest.mark.parametrize("name", list(ZOO))
    def test_traces_cover_every_gemm_layer(self, name):
        model = ZOO[name]
        params, x, plan = _setup(model)
        res = execute_cnn(params, x, plan, _cfg(), impl="ref",
                          lowering=model.graph)
        want = [n.name for n in model.graph.gemm_nodes]
        assert [t.name for t in res.traces] == want
        assert all(t.latency_s > 0 for t in res.traces)

    @pytest.mark.parametrize("backend", ["heana", "amw"])
    def test_depthwise_traces_report_executed_fused_gemm(self, backend):
        """LayerTrace is 'what actually ran', consistent with the tile
        the scheduler sized: under analog carry (HEANA) depthwise layers
        trace the grouped (M, kk*kk, C) taps; under chunk ADC (AMW) the
        fused block-diagonal (M, kk*kk*C, C) GEMM."""
        model = ZOO["mobilenet_mini"]
        acc = pm.AcceleratorConfig.equal_area(backend, Dataflow.OS, 1.0)
        cfg = PhotonicConfig(backend=Backend(backend), bits=6,
                             dpe_size=acc.n, noise_enabled=False)
        params, x, plan = _setup(model, acc=acc)
        res = execute_cnn(params, x, plan, cfg, impl="ref",
                          lowering=model.graph)
        trace = {t.name: t for t in res.traces}["ir2_dw"]
        lplan = {p.name: p for p in plan.layers}["ir2_dw"]
        assert lplan.count == 96 and lplan.d == 1 and lplan.k == 9
        k = 9 if backend == "heana" else 9 * 96
        assert trace.k == k and trace.d == 96         # executed dims
        assert trace.m == lplan.c                     # rows unchanged
        assert trace.block_d == lplan.tile.block_d    # tile fits D=96

    def test_paper_zoo_is_the_four_evaluation_networks(self):
        assert set(PAPER_ZOO) == {"resnet_mini", "mobilenet_mini",
                                  "shufflenet_mini", "googlenet_mini"}
        # each keeps its structural signature
        ops = {n: graph_summary(ZOO[n].graph)["ops"] for n in PAPER_ZOO}
        assert ops["resnet_mini"]["residual_add"] == 3
        assert ops["mobilenet_mini"]["depthwise_conv"] == 3
        assert ops["mobilenet_mini"]["residual_add"] == 1
        assert ops["shufflenet_mini"]["shuffle"] == 2
        assert ops["shufflenet_mini"]["slice"] == 2
        assert ops["shufflenet_mini"]["concat"] == 2
        assert ops["googlenet_mini"]["concat"] == 1


class TestAnalyticConsistency:
    """The runnable lowering and the paper-table accounting (the
    _conv/_dw formulas behind benchmarks/fig11_fps.py's CNN_ZOO tables)
    agree layer by layer — one source of truth."""

    @pytest.mark.parametrize("name", list(ZOO))
    def test_graph_gemms_equal_analytic_tables(self, name):
        model = ZOO[name]
        assert model.gemms() == model.analytic()

    @pytest.mark.parametrize("name", list(ZOO))
    def test_macs_match_and_params_validate(self, name):
        model = ZOO[name]
        analytic_macs = sum(g.macs for g in model.analytic())
        runnable_macs = sum(g.macs for g in model.gemms())
        assert analytic_macs == runnable_macs > 0
        # weight-shape validation path: gemms(params) must agree too
        params = model.init_params(jax.random.PRNGKey(0))
        assert model.gemms(params) == model.analytic()

    @pytest.mark.parametrize("name", list(PAPER_ZOO))
    def test_mini_blocks_mirror_full_tables(self, name):
        """Structural cross-check against the full-size fig11 tables:
        the reduced variant exercises the same layer *kinds* (depthwise
        presence, 1x1/3x3/5x5 kernels) as its full network."""
        full = cnn.CNN_ZOO[name.replace("_mini", "").replace(
            "resnet", "resnet50").replace("mobilenet", "mobilenet_v2")
            .replace("shufflenet", "shufflenet_v2")]()
        mini = ZOO[name].gemms()
        full_has_dw = any(g.count > 1 for g in full)
        mini_has_dw = any(g.count > 1 for g in mini)
        assert full_has_dw == mini_has_dw
        assert mini[0].k == 27          # mini stems are 3x3 on RGB
        assert mini[-1].c == 1          # both end in a classifier fc
        assert full[-1].c == 1


class TestGoldenTrace:
    """Checked-in per-layer fingerprints for a fixed seed: refactors of
    the kernel/scheduler/lowering that silently change numerics fail."""

    PATH = os.path.join(GOLDEN_DIR, "resnet_mini_trace.json")

    def _compute(self):
        model = ZOO["resnet_mini"]
        params, x, plan = _setup(model, batch=2, seed=0)
        res = execute_cnn(params, x, plan, _cfg(), impl="pallas",
                          lowering=model.graph)
        fp = [float(v) for v in np.asarray(res.fingerprints)]
        return {
            "model": "resnet_mini",
            "seed": 0,
            "batch": 2,
            "bits": 6,
            "layers": [n.name for n in model.graph.gemm_nodes],
            "fingerprints": fp,
            "logits_mean_abs": float(np.mean(np.abs(
                np.asarray(res.logits)))),
        }

    def test_golden_fingerprints_match(self):
        with open(self.PATH) as fh:
            golden = json.load(fh)
        got = self._compute()
        assert got["layers"] == golden["layers"]
        np.testing.assert_allclose(
            got["fingerprints"], golden["fingerprints"], rtol=1e-5,
            err_msg="per-layer numerics drifted from the checked-in "
                    "golden trace — if the change is intentional, "
                    "regenerate tests/golden/resnet_mini_trace.json")
        np.testing.assert_allclose(got["logits_mean_abs"],
                                   golden["logits_mean_abs"], rtol=1e-5)


class TestSpatialValidation:
    """Satellite bugfix: spatial dims/pooling used to assume even
    square dims — stride-2 and odd-dimension handling is now explicit."""

    def test_spatial_dims_validates_spec(self):
        assert lw.spatial_dims(16) == (16, 16)
        assert lw.spatial_dims((16, 8)) == (16, 8)
        with pytest.raises(ValueError, match=r"\(H, W\) pair"):
            lw.spatial_dims((16,))
        with pytest.raises(ValueError, match=r"\(H, W\) pair"):
            lw.spatial_dims((16, 8, 3))
        with pytest.raises(ValueError, match="positive"):
            lw.spatial_dims(0)
        with pytest.raises(ValueError, match="positive"):
            lw.spatial_dims((16, -8))

    def test_stride2_conv_handles_odd_dims_explicitly(self):
        """SAME-padded stride-2 convs on odd/rect inputs are first-class
        (out = ceil(in/2)) — no even-dims assumption."""
        g = lw.OpGraph((lw.input_node(2),
                        lw.conv("c", "input", 4, stride=2),
                        lw.pool("gap", "c", kind="global"),
                        lw.fc("out", "gap", 3)))
        params = lw.init_params(g, jax.random.PRNGKey(0), in_hw=(15, 9))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 15, 9, 2))
        got = lw.graph_apply(params, x, g)
        want = lw.direct_forward(params, x, g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-6)
        shapes = lw.infer_shapes(g, (15, 9))
        assert shapes["c"] == (8, 5, 4)

    def test_valid_pool_on_indivisible_dims_raises_clearly(self):
        g = lw.OpGraph((lw.input_node(3),
                        lw.conv("c", "input", 4),
                        lw.pool("p", "c"),
                        lw.fc("out", "p", 2)))
        with pytest.raises(ValueError, match="does not tile H=15"):
            lw.infer_shapes(g, (15, 8))
        with pytest.raises(ValueError, match="does not tile W=9"):
            lw.infer_shapes(g, (16, 9))
        # 'same' pooling is the documented escape hatch
        g2 = lw.OpGraph((lw.input_node(3),
                         lw.conv("c", "input", 4),
                         lw.pool("p", "c", padding="same"),
                         lw.fc("out", "p", 2)))
        assert lw.infer_shapes(g2, (15, 9))["p"] == (8, 5, 4)

    def test_valid_window_larger_than_input_raises(self):
        with pytest.raises(ValueError, match="does not fit"):
            lw.conv_out_dim(2, 3, 1, "valid")

    def test_same_avg_pool_rejected_as_ambiguous(self):
        with pytest.raises(ValueError, match="ambiguous"):
            lw.OpGraph((lw.input_node(3),
                        lw.pool("p", "input", kind="avg",
                                padding="same")))

    def test_graph_structural_validation(self):
        with pytest.raises(ValueError, match="topologically"):
            lw.OpGraph((lw.input_node(3), lw.conv("a", "missing", 4)))
        with pytest.raises(ValueError, match="duplicate"):
            lw.OpGraph((lw.input_node(3), lw.conv("a", "input", 4),
                        lw.conv("a", "input", 4)))
        with pytest.raises(ValueError, match="first node"):
            lw.OpGraph((lw.input_node(3), lw.input_node(3, name="in2")))
        with pytest.raises(ValueError, match="2 input"):
            lw.OpGraph((lw.input_node(3),
                        lw.OpNode("r", "residual_add", ("input",))))

    def test_residual_shape_mismatch_raises_clearly(self):
        g = lw.OpGraph((lw.input_node(3),
                        lw.conv("a", "input", 4),
                        lw.conv("b", "input", 8),
                        lw.residual("r", "a", "b"),
                        lw.fc("out", "r", 2)))
        with pytest.raises(ValueError, match="disagree"):
            lw.infer_shapes(g, 8)

    def test_executor_rejects_wrong_geometry_with_clear_errors(self):
        model = ZOO["googlenet_mini"]
        params, x, plan = _setup(model)
        bad = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
        with pytest.raises(ValueError, match="rows"):
            execute_cnn(params, bad, plan, _cfg(), lowering=model.graph)
        with pytest.raises(ValueError, match="images"):
            execute_cnn(params, x.reshape(2, -1), plan, _cfg(),
                        lowering=model.graph)
