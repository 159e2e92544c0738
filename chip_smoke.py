"""Chip smoke test: the HEANA CNN serving path on a TPU.

Drives the main path the way a user does: ``OperatingPoint.equal_area``
-> ``ServingEngine(params, op)`` -> ``engine.infer(images)``, for the
four paper minis (``models.zoo_cnn.PAPER_ZOO``) at their declared widths
and 32x32 input, with random weights made from a seed.

One chip (the default):

  * HEANA, AMW and MAW engines for every mini at the equal-area points
    with noise off, ``max_batch=256``, answering requests of 1, 5 and 256
    images (buckets 1, 8 and 256).  Each response is checked against the oracle under the
    same compilation: the same engine with the kernel replaced by the
    pure-jnp reference GEMM (``impl="ref"``), same params, same input.
    The bar is ``ORACLE_TOL_ADC_STEPS`` output-ADC steps, 0 (bitwise).
  * Every request is repeated: the repeat must add no trace
    (``exec.trace_count()``) and return the same logits.
  * One noise-on engine (HEANA, resnet_mini): logits finite, and the same
    key gives the same logits twice.

``--chips 4`` runs only data-parallel serving over four chips
(resnet_mini and mobilenet_mini, HEANA, noise off) against a one-device
engine: logits bitwise equal, and the data-parallel result spread over
all four devices.

One process; it starts no other.  It exits non-zero, printing no result,
when JAX finds no TPU, and on any failed check.  The last line of
standard output is ``{"ok": true, "device": {...}}``.

Run:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import hw  # noqa: E402
from repro.core.types import Dataflow  # noqa: E402
from repro.exec import PlanCache, ServingEngine, trace_count  # noqa: E402
from repro.exec.jax_cache import use_persistent_cache  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.zoo_cnn import PAPER_ZOO  # noqa: E402

SEED = 0
MAX_BATCH = 256
REQUEST_SIZES = (1, 5, 256)          # buckets 1, 8 and 256
DP_REQUEST_SIZES = (8, 256)          # bucket sizes four chips divide
DP_CHIPS = 4
#: Allowed |served - oracle| in output-ADC steps of the last GEMM.
ORACLE_TOL_ADC_STEPS = 0
#: (backend, network) engines served with noise off: every mini on the
#: analog-carry kernel (HEANA) and on the per-chunk-ADC kernel (AMW, MAW).
ENGINES = tuple((b, n) for b in ("heana", "amw", "maw") for n in PAPER_ZOO)
NOISE_ENGINE = ("heana", "resnet_mini")
NOISE_REQUEST_SIZES = (1, 5)
DP_NETWORKS = ("resnet_mini", "mobilenet_mini")


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_info(n_chips: int) -> dict:
    """The TPU this run uses; exits non-zero when JAX finds none."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); this script has no CPU fallback")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, JAX found {len(devs)}")
    _check(not ops._on_cpu(), "Pallas kernels would run in interpret mode")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _images(name: str, tag: int, n: int) -> jax.Array:
    model = PAPER_ZOO[name]
    key = jax.random.fold_in(jax.random.PRNGKey(SEED + 1), tag)
    return jax.random.normal(key, (n, *model.in_hw, model.in_ch),
                             jnp.float32)


def _op(backend: str, noise: bool) -> hw.OperatingPoint:
    return hw.OperatingPoint.equal_area(backend, Dataflow.OS, 1.0,
                                        noise_enabled=noise)


def _engine(backend: str, name: str, noise: bool, cache: PlanCache,
            **kw) -> ServingEngine:
    model = PAPER_ZOO[name]
    params = model.init_params(jax.random.PRNGKey(SEED))
    return ServingEngine(params, _op(backend, noise), lowering=model.graph,
                         in_hw=model.in_hw, max_batch=MAX_BATCH,
                         plan_cache=cache, **kw)


def diff_in_adc_steps(got: np.ndarray, want: np.ndarray,
                      adc_bits: int) -> float:
    """Largest |got - want| in output-ADC steps of the last GEMM.

    Each logit is a whole number of ADC steps, at most ``hi`` of them,
    so ``max |want[:, d]| / hi`` bounds column d's step from below and
    the returned count from above.  A difference in an all-zero column
    counts as infinitely many steps.
    """
    levels = (1 << adc_bits) - 1
    hi = levels // 2 + levels % 2
    step = np.abs(want).max(axis=0) / hi
    diff = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        steps = np.where(diff == 0, 0.0, diff / step)
    return float(steps.max())


def serve_and_check(backend: str, name: str, cache: PlanCache) -> None:
    engine = _engine(backend, name, False, cache)
    oracle = _engine(backend, name, False, cache, impl="ref")
    adc_bits = _op(backend, False).kernel_config().adc_bits
    cold_s, warm_ms = 0.0, {}
    equal = total = 0
    max_abs = max_steps = 0.0
    for tag, n in enumerate(REQUEST_SIZES):
        x = _images(name, tag, n)
        t0 = time.perf_counter()
        got = engine.infer(x)
        cold_s += time.perf_counter() - t0
        traces = trace_count()
        t0 = time.perf_counter()
        again = engine.infer(x)
        warm_ms[n] = (time.perf_counter() - t0) * 1e3
        added = trace_count() - traces
        _check(added == 0, f"{backend}/{name}: repeating a {n}-image "
                           f"request traced {added} time(s)")
        got, again = np.asarray(got), np.asarray(again)
        want = np.asarray(oracle.infer(x))
        _check(got.shape == (n, PAPER_ZOO[name].num_classes),
               f"{backend}/{name}: logits shape {got.shape}")
        _check(np.array_equal(got, again),
               f"{backend}/{name}: a repeated request changed its logits")
        equal += int((got == want).sum())
        total += got.size
        max_abs = max(max_abs, float(np.abs(got - want).max()))
        max_steps = max(max_steps, diff_in_adc_steps(got, want, adc_bits))
    print(f"engine {backend}/{name}: cold_s={cold_s:.2f} "
          f"warm_ms={json.dumps({k: round(v, 3) for k, v in warm_ms.items()})} "
          f"exact_share={equal / total:.6f} max_abs_diff={max_abs:.3e} "
          f"max_diff_adc_steps={max_steps:.3g} traces_on_repeat=0",
          flush=True)
    _check(max_steps <= ORACLE_TOL_ADC_STEPS,
           f"{backend}/{name}: served logits differ from the oracle by "
           f"{max_steps:.3g} ADC steps (tolerance {ORACLE_TOL_ADC_STEPS})")


def noise_check(cache: PlanCache) -> None:
    backend, name = NOISE_ENGINE
    engine = _engine(backend, name, True, cache)
    for tag, n in enumerate(NOISE_REQUEST_SIZES):
        x = _images(name, 100 + tag, n)
        key = jax.random.PRNGKey(SEED + 100 + tag)
        a = np.asarray(engine.infer(x, key=key))
        b = np.asarray(engine.infer(x, key=key))
        _check(bool(np.isfinite(a).all()),
               f"noise-on {backend}/{name}: non-finite logits")
        _check(np.array_equal(a, b),
               f"noise-on {backend}/{name}: one key gave two answers")
    print(f"noise-on {backend}/{name}: finite, same key -> same logits "
          f"(requests {list(NOISE_REQUEST_SIZES)})", flush=True)


def data_parallel_check(cache: PlanCache) -> None:
    devs = jax.devices()[:DP_CHIPS]
    for name in DP_NETWORKS:
        dp = _engine("heana", name, False, cache, data_parallel=True,
                     devices=devs)
        one = _engine("heana", name, False, cache, devices=devs[:1])
        for tag, n in enumerate(DP_REQUEST_SIZES):
            x = _images(name, 200 + tag, n)
            got = dp.infer(x)
            spread = got.sharding.device_set
            _check(spread == set(devs),
                   f"dp {name}: a {n}-image request ran on "
                   f"{len(spread)} device(s), not {DP_CHIPS}")
            want = np.asarray(one.infer(x))
            _check(np.array_equal(np.asarray(got), want),
                   f"dp {name}: {n}-image logits differ from one device")
        print(f"dp {name}: buckets {list(DP_REQUEST_SIZES)} over "
              f"{DP_CHIPS} devices, bitwise equal to one device", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, DP_CHIPS), default=1,
                    help=f"{DP_CHIPS}: run only the data-parallel phase")
    args = ap.parse_args(argv)
    device = device_info(args.chips)
    print(f"device_kind={device['kind']} devices={device['count']} "
          f"compile_cache={use_persistent_cache()}", flush=True)
    cache = PlanCache()
    if args.chips == DP_CHIPS:
        data_parallel_check(cache)
    else:
        for backend, name in ENGINES:
            serve_and_check(backend, name, cache)
        noise_check(cache)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
