"""Serving-engine tests (exec.serving — ISSUE 4).

Pins the serving contracts: bucket selection, padding bit-exactness
(a padded request equals the exact-size batch), chunking semantics,
zero retraces after warmup, thread-safety of concurrent serving (and of
the executor's module caches it leans on), micro-batcher plumbing and
error propagation, noise-key handling, data-parallel bit-identity (when
several devices are visible), and the metrics/stats surface.
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (MicroBatcher, PlanCache, ServingEngine, bucket_for,
                        execute_cnn, plan_for_network,
                        power_of_two_buckets, schedule_buckets, trace_count)
from repro.models.cnn import build_small_cnn, lowered_gemms

HEANA = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)


def _cfg(noise: bool = False) -> PhotonicConfig:
    # bits=6 keeps every integer partial sum bit-exactness-safe.
    return PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                          noise_enabled=noise)


@pytest.fixture(scope="module")
def served():
    """One warmed-up engine shared by the module (warmup compiles)."""
    key = jax.random.PRNGKey(0)
    params = build_small_cnn(key)
    engine = ServingEngine(params, HEANA, _cfg(), max_batch=8,
                           plan_cache=PlanCache())
    engine.warmup()
    return key, params, engine


def _images(key, i: int, n: int) -> jnp.ndarray:
    return jax.random.normal(jax.random.fold_in(key, i), (n, 16, 16, 3))


class TestBuckets:
    def test_power_of_two_buckets(self):
        assert power_of_two_buckets(1) == (1,)
        assert power_of_two_buckets(5) == (1, 2, 4, 8)
        assert power_of_two_buckets(8) == (1, 2, 4, 8)
        with pytest.raises(ValueError, match="max_batch"):
            power_of_two_buckets(0)

    def test_bucket_for_picks_smallest_fit(self):
        buckets = (1, 2, 4, 8)
        assert [bucket_for(n, buckets) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]
        with pytest.raises(ValueError, match="exceeds"):
            bucket_for(9, buckets)

    def test_engine_plans_one_per_bucket(self, served):
        _, _, engine = served
        assert set(engine.plans) == set(engine.buckets) == {1, 2, 4, 8}
        for b, plan in engine.plans.items():
            assert plan.batch == b

    def test_schedule_buckets_shares_cache(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        gemms = lowered_gemms(params)
        cache = PlanCache()
        schedule_buckets(gemms, HEANA, (1, 2, 4), cache=cache)
        replans = schedule_buckets(gemms, HEANA, (1, 2, 4), cache=cache)
        assert all(p.cache_misses == 0 for p in replans.values())


class TestBucketedServing:
    @pytest.mark.parametrize("n", [1, 3, 5, 8])
    def test_padded_request_bitwise_equals_exact_batch(self, served, n):
        """Zero padding to the bucket is numerics-neutral: the served
        logits equal an exact-size batch through execute_cnn bitwise."""
        key, params, engine = served
        x = _images(key, 10 + n, n)
        got = engine.infer(x)
        plan = plan_for_network(params, HEANA, batch=n, cache=PlanCache())
        ref = execute_cnn(params, x, plan, _cfg()).logits
        assert got.shape == (n, 10)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_oversize_request_equals_per_chunk_runs(self, served):
        """N > max_bucket chunks into top-bucket pieces; each chunk is
        its own batch (per-batch quantize scale), so the result equals
        the concatenation of exact-size chunk runs."""
        key, params, engine = served
        x = _images(key, 99, 11)                 # chunks: 8 + 3(->4)
        got = engine.infer(x)
        r8 = execute_cnn(params, x[:8], plan_for_network(
            params, HEANA, batch=8, cache=PlanCache()), _cfg()).logits
        r3 = execute_cnn(params, x[8:], plan_for_network(
            params, HEANA, batch=3, cache=PlanCache()), _cfg()).logits
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(jnp.concatenate([r8, r3])))

    def test_zero_retraces_after_warmup(self, served):
        key, _, engine = served
        for n in (1, 2, 3, 8):                   # prime every bucket once
            engine.infer(_images(key, 200 + n, n))
        before = trace_count()
        for n in (1, 2, 3, 4, 5, 7, 8, 11):
            engine.infer(_images(key, 300 + n, n))
        assert trace_count() == before

    def test_retrace_accounting_is_engine_local(self, served):
        """Another engine warming up (new cfg -> new traces) must not
        show up in this engine's retraces_since_warmup."""
        key, params, engine = served
        assert engine.stats()["retraces_since_warmup"] == 0
        other_cfg = PhotonicConfig(backend=Backend.HEANA, bits=7,
                                   dpe_size=83, noise_enabled=False)
        other = ServingEngine(params, HEANA, other_cfg, max_batch=1,
                              plan_cache=engine.plan_cache)
        other.warmup()                          # traces a new executable
        engine.infer(_images(key, 450, 1))
        assert engine.stats()["retraces_since_warmup"] == 0
        assert other.stats()["retraces_since_warmup"] == 0

    def test_infer_one(self, served):
        key, _, engine = served
        img = _images(key, 400, 1)[0]
        one = engine.infer_one(img)
        assert one.shape == (10,)
        np.testing.assert_array_equal(np.asarray(one),
                                      np.asarray(engine.infer(img[None])[0]))

    def test_stats_surface(self, served):
        key, _, engine = served
        engine.infer(_images(key, 500, 3))       # forces padding
        s = engine.stats()
        assert s["requests"] >= 1 and s["images"] >= 3
        assert s["padded_slots"] > 0 and 0 < s["padding_fraction"] < 1
        assert 0 < s["device_wait_s_total"] <= s["infer_s_total"]
        assert s["latency_mean_s"] == pytest.approx(
            s["infer_s_total"] / s["requests"])
        assert s["sustained_ips"] == pytest.approx(
            s["images"] / s["infer_s_total"])
        assert s["warmed_up"] is True
        assert s["plan_cache"]["entries"] > 0
        assert s["compile_cache"]["entries"] > 0
        assert s["buckets"] == [1, 2, 4, 8]


class TestServingErrors:
    """The executor's clear errors surface through the serving entry
    points (ISSUE 4 satellite)."""

    def test_non_image_request(self, served):
        key, _, engine = served
        with pytest.raises(ValueError, match="images"):
            engine.infer(_images(key, 1, 2).reshape(2, -1))
        with pytest.raises(ValueError, match="H, W, C"):
            engine.infer_one(_images(key, 1, 1))

    def test_empty_request(self, served):
        _, _, engine = served
        with pytest.raises(ValueError, match="batch 0"):
            engine.infer(jnp.zeros((0, 16, 16, 3)))

    def test_mismatched_geometry_raises_clearly(self, served):
        """Engine planned for 16x16: an 8x8 request hits the executor's
        geometry validation with its row-count message."""
        key, _, engine = served
        bad = jax.random.normal(key, (2, 8, 8, 3))
        with pytest.raises(ValueError, match="rows"):
            engine.infer(bad)

    def test_batch_mismatch_error_names_serving_engine(self, served):
        """The raw executor's batch-mismatch error now points at the
        bucketing API as the fix."""
        key, params, engine = served
        x5 = _images(key, 2, 5)
        with pytest.raises(ValueError, match="ServingEngine"):
            execute_cnn(params, x5, engine.plans[8], _cfg())

    def test_noise_without_key_raises_through_serving(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        engine = ServingEngine(params, HEANA, _cfg(noise=True),
                               max_batch=2, plan_cache=PlanCache())
        with pytest.raises(ValueError, match="key"):
            engine.infer(jnp.ones((2, 16, 16, 3)))


class TestNoiseServing:
    def test_noisy_serving_reproducible_per_key(self):
        key = jax.random.PRNGKey(0)
        params = build_small_cnn(key)
        engine = ServingEngine(params, HEANA, _cfg(noise=True),
                               max_batch=2, plan_cache=PlanCache())
        engine.warmup()                        # dummy key pre-traces
        x = _images(key, 1, 2)
        before = trace_count()
        r1 = engine.infer(x, key=jax.random.PRNGKey(5))
        r2 = engine.infer(x, key=jax.random.PRNGKey(5))
        r3 = engine.infer(x, key=jax.random.PRNGKey(6))
        assert trace_count() == before         # serving keys reuse warmup
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        assert not np.array_equal(np.asarray(r1), np.asarray(r3))


class TestThreadSafety:
    def test_concurrent_serving_bitwise_and_no_retrace(self, served):
        key, _, engine = served
        xs = [_images(key, 600 + i, (i % 8) + 1) for i in range(8)]
        expect = [np.asarray(engine.infer(x)) for x in xs]
        before = trace_count()
        results = [None] * len(xs)
        errors = []

        def worker(i):
            try:
                results[i] = np.asarray(engine.infer(xs[i]))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        assert trace_count() == before
        for got, want in zip(results, expect):
            np.testing.assert_array_equal(got, want)

    def test_compiled_forward_memo_safe_under_threads(self, served):
        """Hammer the executor wrapper memo from many threads: no
        corruption, bound respected, all callers get a working fn."""
        from repro.exec import executor as ex
        _, params, engine = served
        errors = []

        def worker(seed):
            try:
                for b in engine.buckets:
                    fn = ex.compiled_forward(engine.plans[b], _cfg())
                    assert callable(fn)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        assert ex.compile_cache_stats()["entries"] <= \
            ex.compile_cache_stats()["max_entries"]


class TestMicroBatcher:
    def test_prefilled_batch_rows_match_batched_inference(self, served):
        """Plumbing contract: with the queue pre-filled to exactly one
        bucket, every Future gets its own row of the batched logits."""
        key, _, engine = served
        imgs = [_images(key, 700 + i, 1)[0] for i in range(8)]
        mb = MicroBatcher(engine, max_delay_s=0.05)
        futs = [mb.submit(im) for im in imgs]
        mb.start()
        outs = [f.result(timeout=120) for f in futs]
        mb.stop()
        ref = engine.infer(jnp.stack(imgs))
        for i, out in enumerate(outs):
            assert isinstance(out, np.ndarray)
            np.testing.assert_array_equal(out, np.asarray(ref[i]))
        s = mb.stats()
        assert s["batches_formed"] == 1 and s["requests_batched"] == 8
        assert s["mean_fill"] == 8.0
        assert s["result_reads"] == s["batches_formed"]
        assert s["result_read_s_total"] > 0

    def test_host_and_device_images_give_bitwise_rows(self, served):
        """The same images sent as numpy arrays and as jax Arrays form
        batches whose rows are bitwise equal, and equal to the engine
        run on the stacked batch; every batch is resolved by one read."""
        key, _, engine = served
        imgs = [_images(key, 720 + i, 1)[0] for i in range(3)]
        mb = MicroBatcher(engine, max_delay_s=0.05, max_batch=3)
        futs = ([mb.submit(np.asarray(im)) for im in imgs]
                + [mb.submit(im) for im in imgs])
        mb.start()
        outs = [f.result(timeout=120) for f in futs]
        mb.stop()
        ref = np.asarray(engine.infer(jnp.stack(imgs)))
        for i in range(3):
            np.testing.assert_array_equal(outs[i], outs[i + 3])
            np.testing.assert_array_equal(outs[i], ref[i])
        s = mb.stats()
        assert s["batches_formed"] == 2 and s["mean_fill"] == 3.0
        assert s["result_reads"] == s["batches_formed"]

    def test_concurrent_submitters_all_resolve(self, served):
        key, _, engine = served
        with MicroBatcher(engine, max_delay_s=0.005) as mb:
            futs = []
            lock = threading.Lock()

            def submitter(tid):
                for i in range(3):
                    f = mb.submit(_images(key, 800 + 10 * tid + i, 1)[0])
                    with lock:
                        futs.append(f)

            threads = [threading.Thread(target=submitter, args=(t,))
                       for t in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            outs = [f.result(timeout=120) for f in futs]
        assert len(outs) == 12
        assert all(o.shape == (10,) for o in outs)

    def test_engine_errors_propagate_to_futures(self, served):
        """A bad request fails ITS future, not the worker thread."""
        key, _, engine = served
        with MicroBatcher(engine, max_delay_s=0.0) as mb:
            bad = mb.submit(jnp.zeros((8, 8, 3)))   # wrong geometry
            with pytest.raises(ValueError, match="rows"):
                bad.result(timeout=120)
            good = mb.submit(_images(key, 900, 1)[0])
            assert good.result(timeout=120).shape == (10,)

    def test_mixed_shape_batch_fails_futures_not_worker(self, served):
        """Two different image shapes coalesced into ONE batch make the
        stack fail: those futures error, the worker survives and keeps
        serving."""
        key, _, engine = served
        mb = MicroBatcher(engine, max_delay_s=0.2)
        good_img = _images(key, 910, 1)[0]
        f1 = mb.submit(good_img)
        f2 = mb.submit(jnp.zeros((8, 8, 3)))    # stacks against 16x16
        mb.start()
        with pytest.raises(ValueError):
            f1.result(timeout=120)
        with pytest.raises(ValueError):
            f2.result(timeout=120)
        f3 = mb.submit(good_img)                # worker is still alive
        assert f3.result(timeout=120).shape == (10,)
        mb.stop()

    def test_submit_after_stop_raises(self, served):
        _, _, engine = served
        mb = MicroBatcher(engine).start()
        mb.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            mb.submit(jnp.zeros((16, 16, 3)))

    def test_noise_engine_requires_key(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        engine = ServingEngine(params, HEANA, _cfg(noise=True),
                               max_batch=2, plan_cache=PlanCache())
        with pytest.raises(ValueError, match="key"):
            MicroBatcher(engine)

    def test_validates_image_rank(self, served):
        _, _, engine = served
        with MicroBatcher(engine) as mb:
            with pytest.raises(ValueError, match="H, W, C"):
                mb.submit(jnp.zeros((1, 16, 16, 3)))


class TestDataParallel:
    def test_dp_requires_noise_off(self):
        params = build_small_cnn(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="noise"):
            ServingEngine(params, HEANA, _cfg(noise=True), max_batch=4,
                          plan_cache=PlanCache(), data_parallel=True)

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs >= 2 devices (run under XLA_FLAGS="
                               "--xla_force_host_platform_device_count=4)")
    def test_dp_bitwise_equals_single_device(self, served):
        key, params, engine = served
        n_dev = len(jax.devices())
        if engine.max_bucket % n_dev:
            pytest.skip(f"max bucket {engine.max_bucket} not divisible "
                        f"by {n_dev} devices")
        dp = ServingEngine(params, HEANA, _cfg(), max_batch=8,
                           plan_cache=engine.plan_cache,
                           data_parallel=True)
        dp.warmup()
        x = _images(key, 950, 8)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(dp.infer(x))),
            np.asarray(jax.device_get(engine.infer(x))))
        assert dp.stats()["data_parallel"] is True


class TestGmean:
    def test_empty_suite_raises_clear_valueerror(self):
        with pytest.raises(ValueError, match="empty"):
            pm.gmean([])

    def test_nonempty_unchanged(self):
        assert pm.gmean([2.0, 8.0]) == pytest.approx(4.0)
