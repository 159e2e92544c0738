"""Serving-throughput benchmark: compiled vs eager executor hot path.

The pre-fix executor stalled the device on the HOST every layer
(per-layer ``float(jnp.mean(...))`` syncs) and re-traced every inference
(no jit boundary around the per-layer ``pl.pallas_call``s) — the exact
stalls HEANA's buffer-less in-situ accumulation is designed to avoid on
the real hardware (paper §5, BPCA).  This module measures the fix:

  * warm-call images/sec of the jit-compiled forward
    (exec.compiled_forward) vs the eager op-by-op path
    (execute_cnn(compiled=False)) at batch {1, 32, 256};
  * a no-retrace assertion — warm compiled calls must leave the trace
    counter untouched (exec.trace_count), so the compiled path cannot
    silently regress to eager/retracing;
  * compiled == eager logits bitwise (the numerics contract rides along).

Summaries are cached under experiments/throughput/ for
benchmarks/report.py (§Throughput).  ``--smoke`` runs a small-batch
subset with the same assertions for CI; it exits nonzero on regression.

NOTE on units: images/sec here is the HOST SIMULATION throughput (Pallas
kernel in interpret mode on CPU) — it validates the software hot path.
``modeled_fps`` in the JSONs is the photonic perf-model number for the
same plan; the two are different machines and never directly comparable.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List, Optional, Sequence, Tuple

import jax

from benchmarks.common import Row, device_record
from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, compiled_forward, execute_cnn,
                        plan_for_network, save_summary, throughput_summary,
                        trace_count)
from repro.models.cnn import build_small_cnn

EXP_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "throughput")
BATCHES = (1, 32, 256)
SMOKE_BATCHES = (1, 32)
# Acceptance floor (ISSUE 2): warm compiled must beat eager by >= 5x at
# batch 256.  The smoke floor is looser — CI boxes are noisy — but still
# far above 1.0, so a silent regression to eager (speedup ~1) trips it.
FULL_MIN_SPEEDUP_B256 = 5.0
SMOKE_MIN_SPEEDUP = 2.0


def _time_calls(fn, reps: int) -> float:
    """Median-free best-effort timing: total wall over ``reps`` calls."""
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _reps_for(batch: int, eager: bool) -> int:
    if eager:
        return 1 if batch >= 256 else 2
    return {1: 20, 32: 5}.get(batch, 3)


def measure(batches: Sequence[int] = BATCHES,
            save: bool = True) -> Tuple[List[Row], List[dict], List[str]]:
    """Returns (csv rows, summaries, hard-failure messages)."""
    key = jax.random.PRNGKey(0)
    params = build_small_cnn(key)
    acc = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    # bits=6 keeps every integer partial sum < 2^24 (bit-exactness safe).
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)
    cache = PlanCache()
    rows: List[Row] = []
    summaries: List[dict] = []
    failures: List[str] = []

    for batch in batches:
        x = jax.random.normal(jax.random.fold_in(key, batch),
                              (batch, 16, 16, 3))
        plan = plan_for_network(params, acc, batch=batch, cache=cache)
        fn = compiled_forward(plan, cfg)

        # Cold call compiles; everything after must hit the executable.
        t0 = time.perf_counter()
        fn(params, x, None)[0].block_until_ready()
        cold_s = time.perf_counter() - t0

        traces_before = trace_count()
        reps = _reps_for(batch, eager=False)
        warm_s = _time_calls(
            lambda: fn(params, x, None)[0].block_until_ready(), reps)
        new_traces = trace_count() - traces_before
        if new_traces:
            failures.append(
                f"b{batch}: {new_traces} retraces across {reps} warm "
                f"compiled calls — the compiled path regressed to "
                f"retracing")

        eager_s = _time_calls(
            lambda: execute_cnn(params, x, plan, cfg, compiled=False)
            .block_until_ready(), _reps_for(batch, eager=True))

        # Numerics contract rides along: compiled == eager bitwise.
        c_logits = fn(params, x, None)[0]
        e_logits = execute_cnn(params, x, plan, cfg,
                               compiled=False).logits
        bitexact = bool((c_logits == e_logits).all())
        if not bitexact:
            failures.append(f"b{batch}: compiled logits != eager logits")

        compiled_ips = batch / warm_s
        eager_ips = batch / eager_s
        speedup = compiled_ips / eager_ips
        summary = throughput_summary(
            "small_cnn", batch, compiled_ips, eager_ips, plan.fps,
            extras={"cold_s": cold_s, "warm_s": warm_s,
                    "eager_s": eager_s, "bitexact": bitexact,
                    "retraces_warm": new_traces, "bits": cfg.bits,
                    "device": device_record()})
        summaries.append(summary)
        if save:
            save_summary(summary, EXP_DIR, f"small_cnn_b{batch}.json")
        rows.append(Row(f"throughput/small_cnn/b{batch}/compiled_ips",
                        warm_s * 1e6, round(compiled_ips, 1)))
        rows.append(Row(f"throughput/small_cnn/b{batch}/eager_ips",
                        eager_s * 1e6, round(eager_ips, 1)))
        rows.append(Row(f"throughput/small_cnn/b{batch}/speedup",
                        warm_s * 1e6, round(speedup, 2)))
        rows.append(Row(f"throughput/small_cnn/b{batch}/bitexact",
                        0.0, int(bitexact)))

    no_retrace = not any("retrace" in f for f in failures)
    rows.append(Row("throughput/no_retrace_warm", 0.0, int(no_retrace)))
    return rows, summaries, failures


def run() -> List[Row]:
    """benchmarks/run.py entry point (full grid)."""
    rows, summaries, failures = measure(BATCHES)
    b256 = next((s for s in summaries if s["batch"] == 256), None)
    if b256 is not None:
        ok = b256["speedup"] >= FULL_MIN_SPEEDUP_B256
        rows.append(Row("throughput/b256_speedup_ge_5x", 0.0, int(ok)))
    if failures:
        raise RuntimeError("; ".join(failures))
    return rows


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small-batch subset + assertions for CI; exits "
                         "nonzero if the compiled path regressed")
    args = ap.parse_args(argv)
    batches = SMOKE_BATCHES if args.smoke else BATCHES
    rows, summaries, failures = measure(batches, save=not args.smoke)
    for r in rows:
        print(r.csv())
    status = 0
    for s in summaries:
        floor = SMOKE_MIN_SPEEDUP if args.smoke else (
            FULL_MIN_SPEEDUP_B256 if s["batch"] == 256 else 1.0)
        if s["speedup"] < floor:
            print(f"FAIL: b{s['batch']} compiled/eager speedup "
                  f"{s['speedup']:.2f}x < {floor}x floor", file=sys.stderr)
            status = 1
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
        status = 1
    if status == 0:
        print("throughput: compiled path OK (no retraces, bit-exact, "
              f"speedups {[round(s['speedup'], 1) for s in summaries]})")
    return status


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    raise SystemExit(main())
