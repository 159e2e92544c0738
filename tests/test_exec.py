"""Execution-engine tests: scheduler optimality, plan cache, executor
parity with the pure-jnp oracle (paper §4/§6.3 — flexible dataflows),
and the jit-compiled serving hot path (ISSUE 2): kernel == oracle
bitwise under one compilation, zero retraces on warm calls, lazy trace
materialization."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dataflow as df
from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, compiled_forward, execute_cnn,
                        plan_for_network, plan_layer, plan_summary,
                        plan_table, plan_vs_fixed, reference_forward,
                        schedule_cnn, trace_count)
from repro.exec.scheduler import choose_tile
from repro.kernels import ops
from repro.models import cnn
from repro.models import lowering as lw
from repro.models.cnn import CNN_ZOO, LayerGemm, build_small_cnn

HEANA = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
AMW = pm.AcceleratorConfig.equal_area("amw", Dataflow.WS, 1.0)


class TestScheduler:
    def test_picks_perf_model_argmin_per_layer(self):
        """The planned dataflow is exactly the gemm_cost argmin."""
        for layer in (LayerGemm("fat_k", 64, 4096, 64),     # fat contraction
                      LayerGemm("fat_c", 8192, 64, 64),     # fat rows
                      LayerGemm("fc", 1, 2048, 1000)):
            for acc in (HEANA, AMW):
                plan = plan_layer(layer, acc, cache=PlanCache())
                g = df.GemmShape(layer.c, layer.k, layer.d)
                want = min(
                    Dataflow,
                    key=lambda f: (pm.gemm_cost(
                        g, dataclasses.replace(acc, dataflow=f)).latency_s,
                        pm.gemm_cost(
                        g, dataclasses.replace(acc, dataflow=f)).energy.total,
                        list(Dataflow).index(f)))
                assert plan.dataflow == want, (layer.name, acc.backend)

    def test_amw_fc_layer_prefers_input_stationary(self):
        """Known shape: a C=1 GEMM on a thermo-optic backend holds the one
        input row; IS ties WS on latency and wins the energy tie-break
        (one DAC-held input row vs re-streaming inputs per column tile)."""
        plan = plan_layer(LayerGemm("fc", 1, 2048, 1000), AMW,
                          cache=PlanCache())
        assert plan.dataflow == Dataflow.IS
        assert plan.candidates["is"] <= plan.candidates["ws"]

    def test_amw_batched_conv_prefers_weight_stationary(self):
        """Fat-C conv on AMW amortizes the 4us thermo-optic weight hold."""
        plan = plan_layer(LayerGemm("conv", 12544, 147, 64), AMW, batch=256,
                          cache=PlanCache())
        assert plan.dataflow == Dataflow.WS

    @pytest.mark.parametrize("name", list(CNN_ZOO))
    @pytest.mark.parametrize("batch", [1, 256])
    def test_auto_plan_at_least_best_fixed(self, name, batch):
        """Acceptance: auto-scheduled FPS >= best single fixed dataflow."""
        layers = CNN_ZOO[name]()
        for acc in (HEANA, AMW):
            plan = schedule_cnn(layers, acc, batch, cache=PlanCache())
            best = max(pm.cnn_inference(
                layers, dataclasses.replace(acc, dataflow=f), batch).fps
                for f in Dataflow)
            assert plan.fps >= best * (1 - 1e-12), (name, acc.backend, batch)

    def test_planned_totals_match_perf_model(self):
        """CnnPlan.result is literally cnn_inference under the plan's flows."""
        layers = CNN_ZOO["shufflenet_v2"]()
        plan = schedule_cnn(layers, HEANA, 1, cache=PlanCache())
        want = pm.cnn_inference(layers, HEANA, 1,
                                dataflows=list(plan.dataflows))
        assert plan.fps == want.fps
        assert plan.latency_s == want.latency_s

    def test_tile_choice_lane_aligned_and_covering(self):
        for m, d, k in ((1, 10, 2048), (784, 128, 864), (12544, 64, 147)):
            t = choose_tile(m, d, k, dpe_size=83)
            assert t.block_m % 8 == 0 and t.block_d % 128 == 0
            assert t.grid_m * t.block_m >= m
            assert t.grid_d * t.block_d >= d
            assert t.pad_waste >= 0.0


class TestPlanCache:
    def test_repeated_shapes_hit_within_one_cnn(self):
        cache = PlanCache()
        plan = schedule_cnn(CNN_ZOO["resnet50"](), HEANA, 1, cache=cache)
        assert plan.cache_hits > 0          # bottleneck blocks repeat shapes
        assert plan.cache_hits + plan.cache_misses == len(plan.layers)

    def test_replan_is_all_hits_and_identical(self):
        cache = PlanCache()
        p1 = schedule_cnn(CNN_ZOO["googlenet"](), HEANA, 1, cache=cache)
        p2 = schedule_cnn(CNN_ZOO["googlenet"](), HEANA, 1, cache=cache)
        assert p2.cache_hits == len(p2.layers) and p2.cache_misses == 0
        assert p1.dataflows == p2.dataflows
        assert p1.fps == p2.fps

    def test_key_sensitive_to_shape_config_objective(self):
        cache = PlanCache()
        base = plan_layer(LayerGemm("l", 64, 256, 64), HEANA, cache=cache)
        other_shape = plan_layer(LayerGemm("l", 64, 256, 65), HEANA,
                                 cache=cache)
        other_acc = plan_layer(LayerGemm("l", 64, 256, 64), AMW, cache=cache)
        other_obj = plan_layer(LayerGemm("l", 64, 256, 64), HEANA,
                               objective="energy", cache=cache)
        keys = {base.cache_key, other_shape.cache_key, other_acc.cache_key,
                other_obj.cache_key}
        assert len(keys) == 4
        assert cache.stats()["hits"] == 0

    def test_name_does_not_enter_the_key(self):
        cache = PlanCache()
        a = plan_layer(LayerGemm("alpha", 64, 256, 64), HEANA, cache=cache)
        b = plan_layer(LayerGemm("beta", 64, 256, 64), HEANA, cache=cache)
        assert a.cache_key == b.cache_key
        assert b.cache_hit and not a.cache_hit
        assert b.name == "beta"             # name re-attached on hit

    def test_dump_load_roundtrip(self, tmp_path):
        cache = PlanCache()
        schedule_cnn(CNN_ZOO["mobilenet_v2"](), HEANA, 1, cache=cache)
        path = str(tmp_path / "plans.json")
        cache.dump(path)
        fresh = PlanCache()
        assert fresh.load(path) == len(cache)
        plan = schedule_cnn(CNN_ZOO["mobilenet_v2"](), HEANA, 1, cache=fresh)
        assert plan.cache_misses == 0


class TestExecutor:
    def _setup(self, noise=False, bits=6):
        key = jax.random.PRNGKey(0)
        params = build_small_cnn(key)
        x = jax.random.normal(jax.random.fold_in(key, 1), (3, 16, 16, 3))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=bits, dpe_size=83,
                             noise_enabled=noise)
        plan = plan_for_network(params, HEANA, batch=3, cache=PlanCache())
        return params, x, cfg, plan

    def test_pallas_execution_bit_exact_vs_oracle(self):
        """Acceptance: end-to-end Pallas inference == jnp reference exactly
        with noise disabled (bits=6 keeps every partial sum < 2^24)."""
        params, x, cfg, plan = self._setup()
        res = execute_cnn(params, x, plan, cfg, impl="pallas")
        ref = reference_forward(params, x, cfg)
        np.testing.assert_array_equal(np.asarray(res.logits),
                                      np.asarray(ref))

    def test_ref_impl_matches_models_own_forward(self):
        """Executor lowering is faithful to small_cnn_apply itself."""
        params, x, cfg, plan = self._setup()
        res = execute_cnn(params, x, plan, cfg, impl="ref")
        ref = reference_forward(params, x, cfg)
        np.testing.assert_array_equal(np.asarray(res.logits),
                                      np.asarray(ref))

    def test_noise_keys_reproducible_per_layer(self):
        params, x, cfg, plan = self._setup(noise=True)
        r1 = execute_cnn(params, x, plan, cfg, key=jax.random.PRNGKey(5))
        r2 = execute_cnn(params, x, plan, cfg, key=jax.random.PRNGKey(5))
        r3 = execute_cnn(params, x, plan, cfg, key=jax.random.PRNGKey(6))
        np.testing.assert_array_equal(np.asarray(r1.logits),
                                      np.asarray(r2.logits))
        assert not np.array_equal(np.asarray(r1.logits),
                                  np.asarray(r3.logits))

    def test_traces_carry_plan_and_numerics(self):
        params, x, cfg, plan = self._setup()
        res = execute_cnn(params, x, plan, cfg, impl="ref",
                          collect_activations=True)
        assert [t.name for t in res.traces] == ["conv1", "conv2", "conv3",
                                                "fc"]
        assert all(t.latency_s > 0 for t in res.traces)
        assert len(res.activations) == 4
        assert res.logits.shape == (3, 10)

    def test_plan_lowering_mismatch_raises(self):
        params, x, cfg, _ = self._setup()
        bad = schedule_cnn([LayerGemm("only", 256, 27, 16)], HEANA,
                           cache=PlanCache())
        with pytest.raises(ValueError, match="lowering"):
            execute_cnn(params, x, bad, cfg)

    def test_batch_mismatch_raises(self):
        params, x, cfg, plan = self._setup()       # plan at batch 3
        x8 = jnp.concatenate([x, x, x[:2]], axis=0)
        with pytest.raises(ValueError, match="batch"):
            execute_cnn(params, x8, plan, cfg)

    def test_lowered_gemms_rejects_wrong_in_hw(self):
        params = build_small_cnn(jax.random.PRNGKey(0), in_hw=32)
        with pytest.raises(ValueError, match="in_hw"):
            cnn.lowered_gemms(params)              # default in_hw=16
        gemms = cnn.lowered_gemms(params, in_hw=32)
        assert gemms[0].c == 32 * 32

    def test_lowered_gemms_match_forward_shapes(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        gemms = cnn.lowered_gemms(params)
        assert [(g.name, g.c, g.k, g.d) for g in gemms] == [
            ("conv1", 256, 27, 16), ("conv2", 64, 144, 32),
            ("conv3", 16, 288, 32), ("fc", 1, 512, 10)]


def _custom_lowering():
    """A runnable network that is NOT the small CNN: two convs (one 5x5),
    one pool, fc — exercises the lowering-driven oracle (ISSUE 2 satellite:
    reference_forward used to hardcode small_cnn_apply)."""
    return lw.OpGraph((
        lw.input_node(2),
        lw.conv("ca", "input", 8, kk=3),
        lw.pool("ca.pool", "ca"),
        lw.conv("cb", "ca.pool", 12, kk=5),
        lw.fc("out", "cb", 5),
    ))


def _custom_params(key, in_hw=8, in_ch=2):
    k1, k2, k3 = jax.random.split(key, 3)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32) \
        / jnp.sqrt(shape[0])
    return {
        "ca": mk(k1, (in_ch * 9, 8)),
        "cb": mk(k2, (8 * 25, 12)),
        "out": mk(k3, ((in_hw // 2) ** 2 * 12, 5)),
    }


class TestCompiledForward:
    """The serving hot path: the compiled Pallas forward equals the
    compiled oracle forward bitwise, and warm calls never retrace."""

    def _setup(self, batch=3, noise=False, bits=6):
        key = jax.random.PRNGKey(0)
        params = build_small_cnn(key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (batch, 16, 16, 3))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=bits, dpe_size=83,
                             noise_enabled=noise)
        plan = plan_for_network(params, HEANA, batch=batch,
                                cache=PlanCache())
        return params, x, cfg, plan

    @pytest.mark.parametrize("noise", [False, True])
    @pytest.mark.parametrize("batch", [1, 3])
    def test_compiled_bit_exact_vs_eager_pallas(self, noise, batch):
        """Acceptance: kernel == oracle under one compilation — the
        compiled impl='pallas' forward equals the compiled impl='ref'
        forward bitwise (same key), noise on and off."""
        params, x, cfg, plan = self._setup(batch=batch, noise=noise)
        key = jax.random.PRNGKey(11) if noise else None
        c = execute_cnn(params, x, plan, cfg, key=key, impl="pallas")
        r = execute_cnn(params, x, plan, cfg, key=key, impl="ref")
        np.testing.assert_array_equal(np.asarray(c.logits),
                                      np.asarray(r.logits))
        # fingerprints are diagnostics: the two programs may fuse their
        # reduces differently — tight tolerance only
        np.testing.assert_allclose(np.asarray(c.fingerprints),
                                   np.asarray(r.fingerprints), rtol=5e-6)

    @pytest.mark.parametrize("noise", [False, True])
    def test_compiled_bit_exact_vs_eager_batch256(self, noise):
        """Acceptance at the serving batch (256): compiled impl='pallas'
        == compiled impl='ref' bitwise, both on the batch-256 plan's
        tilings."""
        params = build_small_cnn(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(2), (256, 16, 16, 3))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                             noise_enabled=noise)
        plan = plan_for_network(params, HEANA, batch=256,
                                cache=PlanCache())
        key = jax.random.PRNGKey(11) if noise else None
        c = execute_cnn(params, x, plan, cfg, key=key, impl="pallas")
        r = execute_cnn(params, x, plan, cfg, key=key, impl="ref")
        np.testing.assert_array_equal(np.asarray(c.logits),
                                      np.asarray(r.logits))

    @pytest.mark.parametrize("batch", [1, 3, 32, 256])
    def test_no_retrace_on_repeated_calls(self, batch):
        """Acceptance: warm compiled calls never re-trace (the pre-fix
        executor re-traced every inference), at every batch of the grid
        from one image to the serving batch."""
        params, x, cfg, plan = self._setup(batch=batch)
        execute_cnn(params, x, plan, cfg)           # cold: traces once
        before = trace_count()
        for _ in range(3):
            execute_cnn(params, x, plan, cfg)
        assert trace_count() == before
        # a replanned (equal) plan must hit the same executable
        plan2 = plan_for_network(params, HEANA, batch=batch,
                                 cache=PlanCache())
        execute_cnn(params, x, plan2, cfg)
        assert trace_count() == before

    def test_new_batch_shape_traces_once(self):
        params, x, cfg, plan = self._setup()
        execute_cnn(params, x, plan, cfg)
        before = trace_count()
        plan8 = plan_for_network(params, HEANA, batch=8, cache=PlanCache())
        x8 = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 16, 3))
        execute_cnn(params, x8, plan8, cfg)
        assert trace_count() == before + 1          # one new shape: 1 trace
        execute_cnn(params, x8, plan8, cfg)
        assert trace_count() == before + 1

    def test_compiled_forward_memo_shares_wrapper(self):
        """Equal planning problems (distinct plan objects) share one
        compiled wrapper (content-addressed memo)."""
        params, x, cfg, plan = self._setup()
        plan2 = plan_for_network(params, HEANA, batch=3, cache=PlanCache())
        assert plan is not plan2
        assert compiled_forward(plan, cfg) is compiled_forward(plan2, cfg)

    def test_compiled_forward_memo_is_bounded(self):
        """The wrapper memo is LRU-bounded (serving processes must not
        grow without limit)."""
        from repro.exec import executor as ex
        params, _, cfg, plan = self._setup()
        compiled_forward(plan, cfg)
        assert len(ex._FORWARD_CACHE) <= ex._FORWARD_CACHE_MAX

    def test_plans_are_hashable_and_value_equal(self):
        """CnnPlan/LayerPlan/TileChoice serve as static jit args."""
        params, _, _, plan = self._setup()
        plan2 = plan_for_network(params, HEANA, batch=3, cache=PlanCache())
        assert hash(plan) == hash(plan2) and plan == plan2
        assert hash(plan.layers[0]) == hash(plan2.layers[0])
        assert hash(plan.layers[0].tile) == hash(plan2.layers[0].tile)
        other = plan_for_network(params, HEANA, batch=4, cache=PlanCache())
        assert plan != other
        with pytest.raises(TypeError, match="immutable"):
            plan.layers[0].candidates["os"] = 0.0

    def test_traces_materialize_lazily(self):
        params, x, cfg, plan = self._setup()
        res = execute_cnn(params, x, plan, cfg)
        assert res._traces is None                  # nothing synced yet
        assert res.fingerprints.shape == (len(plan.layers),)
        traces = res.traces                         # first access builds
        assert res._traces is traces
        assert [t.name for t in traces] == ["conv1", "conv2", "conv3", "fc"]
        assert all(t.out_mean_abs > 0 for t in traces)

    def test_fc_trace_m_is_batch_rows_not_placeholder(self):
        """Satellite fix: fc layers used to trace m=-1."""
        params, x, cfg, plan = self._setup(batch=3)
        res = execute_cnn(params, x, plan, cfg)
        fc = res.traces[-1]
        assert fc.name == "fc" and fc.m == 3        # batch folded into M
        assert all(t.m > 0 for t in res.traces)


class TestOracleLowering:
    """reference_forward drives the SAME lowering the executor runs."""

    def _setup(self, noise=False):
        key = jax.random.PRNGKey(4)
        lowering = _custom_lowering()
        params = _custom_params(key)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 8, 2))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                             noise_enabled=noise)
        plan = plan_for_network(params, HEANA, batch=2, in_hw=8,
                                lowering=lowering, cache=PlanCache())
        return params, x, cfg, plan, lowering

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_executor_matches_oracle_on_non_small_lowering(self, impl):
        params, x, cfg, plan, lowering = self._setup()
        res = execute_cnn(params, x, plan, cfg, impl=impl,
                          lowering=lowering)
        ref = reference_forward(params, x, cfg, lowering=lowering)
        np.testing.assert_array_equal(np.asarray(res.logits),
                                      np.asarray(ref))
        assert res.logits.shape == (2, 5)

    def test_oracle_differs_from_small_cnn_apply(self):
        """Guard against the old bug: the oracle is NOT the small CNN."""
        params, x, cfg, _, lowering = self._setup()
        ref = reference_forward(params, x, cfg, lowering=lowering)
        with pytest.raises(Exception):
            # driving these params through the small-CNN structure is a
            # shape error — exactly what the hardcoded oracle used to hide
            cnn.small_cnn_apply(params, x)
        assert ref.shape == (2, 5)


class TestRectangularInputs:
    """The executor used to assume H == W (hw = x.shape[1])."""

    def _setup(self, h=16, w=8):
        key = jax.random.PRNGKey(5)
        # small-CNN convs are spatial-size agnostic; swap in a
        # rect-compatible fc ((h//4)*(w//4)*32 inputs after two pools)
        params = dict(build_small_cnn(key))
        params["fc"] = jax.random.normal(jax.random.fold_in(key, 9),
                                         ((h // 4) * (w // 4) * 32, 10),
                                         jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, h, w, 3))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                             noise_enabled=False)
        return params, x, cfg

    def test_rectangular_input_matches_oracle(self):
        params, x, cfg = self._setup()
        plan = plan_for_network(params, HEANA, batch=2, in_hw=(16, 8),
                                cache=PlanCache())
        res = execute_cnn(params, x, plan, cfg, impl="ref")
        ref = reference_forward(params, x, cfg)
        np.testing.assert_array_equal(np.asarray(res.logits),
                                      np.asarray(ref))
        assert res.logits.shape == (2, 10)

    def test_square_plan_on_rect_input_raises_clearly(self):
        params, x, cfg = self._setup()
        square = plan_for_network(build_small_cnn(jax.random.PRNGKey(5)),
                                  HEANA, batch=2, cache=PlanCache())
        with pytest.raises(ValueError, match="rows"):
            execute_cnn(params, x, square, cfg)

    def test_odd_spatial_dim_pooling_raises(self):
        params, x, cfg = self._setup()
        with pytest.raises(ValueError, match="does not tile H=15"):
            cnn.lowered_gemms(params, in_hw=(15, 8))
        plan = plan_for_network(params, HEANA, batch=2, in_hw=(16, 8),
                                cache=PlanCache())
        x_odd = jax.random.normal(jax.random.PRNGKey(2), (2, 15, 8, 3))
        with pytest.raises(ValueError, match="does not tile|rows"):
            execute_cnn(params, x_odd, plan, cfg)

    def test_non_image_input_raises(self):
        params, x, cfg = self._setup()
        plan = plan_for_network(params, HEANA, batch=2, in_hw=(16, 8),
                                cache=PlanCache())
        with pytest.raises(ValueError, match="images"):
            execute_cnn(params, x.reshape(2, -1), plan, cfg)


class TestPlanCacheHardening:
    """Atomic dump, tolerant load, LRU bound (serving-deployment fixes)."""

    def test_corrupt_file_loads_zero_not_raises(self, tmp_path):
        path = tmp_path / "plans.json"
        path.write_text('{"truncated": ')           # crash-mid-write relic
        cache = PlanCache()
        with pytest.warns(RuntimeWarning, match="unreadable"):
            assert cache.load(str(path)) == 0
        assert len(cache) == 0
        # cache still fully usable afterwards
        plan = schedule_cnn(CNN_ZOO["shufflenet_v2"](), HEANA, 1,
                            cache=cache)
        assert plan.cache_misses > 0

    def test_malformed_entries_skipped_valid_merged(self, tmp_path):
        cache = PlanCache()
        schedule_cnn(CNN_ZOO["mobilenet_v2"](), HEANA, 1, cache=cache)
        path = str(tmp_path / "plans.json")
        cache.dump(path)
        blob = json.load(open(path))
        n_valid = len(blob)
        blob["bad-entry"] = {"not": "a plan"}
        blob["worse"] = 17
        json.dump(blob, open(path, "w"))
        fresh = PlanCache()
        with pytest.warns(RuntimeWarning, match="malformed"):
            assert fresh.load(path) == n_valid
        plan = schedule_cnn(CNN_ZOO["mobilenet_v2"](), HEANA, 1,
                            cache=fresh)
        assert plan.cache_misses == 0

    def test_dump_replaces_atomically_no_temp_left(self, tmp_path):
        cache = PlanCache()
        schedule_cnn(CNN_ZOO["shufflenet_v2"](), HEANA, 1, cache=cache)
        path = tmp_path / "plans.json"
        path.write_text('{"stale": true}')
        cache.dump(str(path))
        assert json.load(open(path)) != {"stale": True}
        leftovers = [p for p in tmp_path.iterdir() if p.name != path.name]
        assert leftovers == []

    def test_non_dict_json_loads_zero(self, tmp_path):
        path = tmp_path / "plans.json"
        path.write_text('[1, 2, 3]')
        with pytest.warns(RuntimeWarning, match="not a JSON object"):
            assert PlanCache().load(str(path)) == 0

    def test_lru_bound_evicts_oldest(self):
        cache = PlanCache(max_entries=2)
        a = plan_layer(LayerGemm("a", 64, 256, 64), HEANA, cache=cache)
        plan_layer(LayerGemm("b", 64, 256, 65), HEANA, cache=cache)
        # touch a so b is the LRU entry
        assert cache.get(a.cache_key) is not None
        plan_layer(LayerGemm("c", 64, 256, 66), HEANA, cache=cache)
        assert len(cache) == 2
        assert cache.stats()["evictions"] == 1
        assert cache.get(a.cache_key) is not None   # survived (recently used)
        re_b = plan_layer(LayerGemm("b", 64, 256, 65), HEANA, cache=cache)
        assert not re_b.cache_hit                   # b was evicted

    def test_max_entries_validation(self):
        with pytest.raises(ValueError, match="max_entries"):
            PlanCache(max_entries=0)

    def test_load_never_overstates_retained(self, tmp_path):
        """A dump larger than max_entries merges a truncated tail and
        returns what actually survived, with a warning."""
        big = PlanCache()
        schedule_cnn(CNN_ZOO["mobilenet_v2"](), HEANA, 1, cache=big)
        assert len(big) > 2
        path = str(tmp_path / "plans.json")
        big.dump(path)
        small = PlanCache(max_entries=2)
        with pytest.warns(RuntimeWarning, match="merging only"):
            loaded = small.load(path)
        assert loaded == 2 == len(small)

    def test_dump_preserves_lru_order_through_overflow(self, tmp_path):
        """ISSUE 4 satellite: dump used sort_keys on the store, so an
        overflowing load trimmed a LEXICOGRAPHIC subset of the sha256
        keys instead of the most-recently-used entries it promises.
        Round trip: touch a known subset, dump, load into a smaller
        cache — exactly the MRU entries must survive."""
        big = PlanCache()
        plans = [plan_layer(LayerGemm(f"l{i}", 64, 256, 64 + i), HEANA,
                            cache=big) for i in range(8)]
        # Touch 3 entries (spread across the key space) to make them MRU.
        mru = [plans[i].cache_key for i in (5, 0, 3)]
        for k in mru:
            assert big.get(k) is not None
        path = str(tmp_path / "plans.json")
        big.dump(path)
        small = PlanCache(max_entries=3)
        with pytest.warns(RuntimeWarning, match="merging only"):
            assert small.load(path) == 3
        for k in mru:                    # the touched (MRU) set survived
            assert small.get(k) is not None
        lru_keys = {p.cache_key for p in plans} - set(mru)
        for k in lru_keys:
            assert small.get(k) is None

    def test_degenerate_adc_full_scale_does_not_crash(self):
        """adc_round keeps adc_readout's floor: fs=0 clamps, no div-zero."""
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                             noise_enabled=False)
        out = ops.photonic_matmul(x, w, cfg, impl="ref", adc_fs=0.0)
        assert bool(jnp.all(jnp.isfinite(out)))


class TestNoiseKeyValidation:
    """noise_enabled=True + key=None must fail loudly, not run silent."""

    def _cfg(self, noise=True):
        return PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                              noise_enabled=noise)

    def test_photonic_matmul_raises_without_key(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        with pytest.raises(ValueError, match="noise_enabled"):
            ops.photonic_matmul(x, w, self._cfg())

    def test_photonic_matmul_ok_with_key_or_noise_off(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        noisy = ops.photonic_matmul(x, w, self._cfg(),
                                    key=jax.random.PRNGKey(2), impl="ref")
        clean = ops.photonic_matmul(x, w, self._cfg(noise=False),
                                    impl="ref")
        assert noisy.shape == clean.shape == (4, 8)
        assert not np.array_equal(np.asarray(noisy), np.asarray(clean))

    def test_execute_cnn_raises_without_key(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        plan = plan_for_network(params, HEANA, batch=2, cache=PlanCache())
        with pytest.raises(ValueError, match="noise_enabled"):
            execute_cnn(params, x, plan, self._cfg())

    def test_reference_forward_rejects_noisy_cfg(self):
        """The oracle is deterministic by definition — a noise-enabled cfg
        without a key can't silently run noiseless anymore."""
        params = build_small_cnn(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        with pytest.raises(ValueError, match="noise_enabled"):
            reference_forward(params, x, self._cfg())


class TestReport:
    def test_summary_and_table_render(self):
        plan = schedule_cnn(CNN_ZOO["googlenet"](), HEANA, 1,
                            cache=PlanCache())
        s = plan_summary(plan, "googlenet")
        assert s["n_layers"] == len(plan.layers)
        assert sum(s["dataflow_mix"].values()) == len(plan.layers)
        assert abs(s["fps"] - plan.fps) < 1e-9
        table = plan_table(plan, max_rows=3)
        assert table.count("\n") >= 4
        fixed = {f: pm.cnn_inference(
            CNN_ZOO["googlenet"](), dataclasses.replace(HEANA, dataflow=f)
            ).fps for f in Dataflow}
        cmp = plan_vs_fixed(plan, fixed)
        assert cmp["uplift"] >= 1.0 - 1e-12


@pytest.mark.parametrize("hw", [(7, 7), (4, 4), (3, 5), (1, 1)])
def test_global_mean_adds_rows_then_halves_columns(hw):
    """The pool's add order, pinned: rows in sequence, then the columns,
    zero-padded to a power of two, by pairwise halving (the order of
    XLA's reduce on a TPU v5e, which bench/reference.py's jnp.mean
    takes there), eagerly and under jit."""
    h, w = hw
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(h * 10 + w),
                                      (3, h, w, 130)), np.float32)
    v = x[:, 0].copy()
    for i in range(1, h):
        v = v + x[:, i]
    width = 1 << (w - 1).bit_length()
    v = np.concatenate([v, np.zeros((3, width - w, 130), np.float32)], 1)
    while v.shape[1] > 1:
        v = v[:, :v.shape[1] // 2] + v[:, v.shape[1] // 2:]
    want = (v * np.float32(1.0 / (h * w))).reshape(3, 1, 1, 130)
    for fn in (lw._global_mean, jax.jit(lw._global_mean)):
        np.testing.assert_array_equal(np.asarray(fn(jnp.asarray(x))), want)
