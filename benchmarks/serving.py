"""Serving-engine benchmark: bucketed/batched serving vs per-request
execution (exec.serving — ISSUE 4).

The per-request baseline is what a naive front-end would do with the
executor: a batch-1 plan and one compiled ``execute_cnn`` call per
arriving image, blocking for each result.  The serving engine amortizes
the per-call overhead by coalescing traffic into power-of-two batch
buckets, each pre-traced at ``warmup()``, and is thread-safe — so the
sustained number is measured the way a real front-end would run it:
a couple of request worker threads streaming max-bucket batches
(pipelined dispatch), exactly the concurrency the executor-cache locks
of this PR make safe.  Measured contrasts:

  * **bucketed_ips** — sustained warm images/sec, 2 worker threads
    streaming max-bucket batches through ``ServingEngine.infer`` (a
    mixed-size stream follows to exercise padding, whose overhead
    fraction rides along in the stats);
  * **per_request_ips** — warm single-image blocking ``execute_cnn``;
  * **zero retraces** after warmup across all bucket reuse (trace_count
    pinned — a regression to per-shape tracing trips the gate);
  * **data-parallel bit-identity** — with >= 2 devices (CI forces 4
    virtual CPU devices via XLA_FLAGS), the NamedSharding data-parallel
    path must return logits bitwise equal to single-device (noise off).

Networks are zoo graphs served at 16x16 (the engine's ``in_hw`` knob):
small request tensors are the regime the serving layer exists for — the
Mixed-Sized Tensors observation (PAPERS.md, arXiv:2207.05278) — and at
32x32 the host-simulation compute swamps the per-request overhead the
engine amortizes.  Acceptance (full run): bucketed serving sustains
>= 5x per-request throughput on at least two zoo networks.  ``--smoke``
runs reduced reps with a looser floor for CI and exits nonzero on any
contract breach.

NOTE on units: images/sec is HOST SIMULATION throughput (Pallas kernel
in interpret mode on CPU) — it validates the serving software path, not
the photonic perf model's FPS.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax

from benchmarks.common import Row, device_record
from repro.core import perf_model as pm
from repro.core.types import Backend, Dataflow, PhotonicConfig
from repro.exec import (PlanCache, ServingEngine, execute_cnn,
                        save_summary, serving_summary, trace_count)
from repro.models import lowering as lw
from repro.models.zoo_cnn import ZOO

EXP_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "serving")
# Floor-eligible networks (acceptance: >= 5x on at least two of them in
# the full run; smoke streams the first two with a looser per-network
# floor) + an extra coverage cell.  The >= 5x floor applies to the
# plain single-device environment — forcing virtual host devices
# (XLA_FLAGS) splits the host cores and dampens the concurrent-stream
# gain, which is why the floor run and the dp-evidence run are separate
# rows (artifacts are keyed by device count).
NETWORKS = ("mobilenet_mini", "small_cnn", "shufflenet_mini")
SMOKE_NETWORKS = NETWORKS[:2]
FULL_EXTRA_NETWORKS = ("googlenet_mini",)
IN_HW = 16
MAX_BATCH = 16
STREAM_THREADS = 2
FULL_MIN_SPEEDUP = 5.0
SMOKE_MIN_SPEEDUP = 2.0


def _stream_ips(engine: ServingEngine, batches: List, threads: int) -> float:
    """Sustained warm throughput: ``threads`` workers each streaming the
    given batches with pipelined dispatch (block only at the end)."""
    def worker():
        outs = [engine.infer(x, block=False) for x in batches]
        outs[-1].block_until_ready()

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    n_images = threads * sum(x.shape[0] for x in batches)
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return n_images / (time.perf_counter() - t0)


def _measure_network(name: str, cache: PlanCache, reps: int,
                     smoke: bool) -> Tuple[dict, List[str]]:
    """One network's serving measurement; returns (summary, failures)."""
    failures: List[str] = []
    zoo = ZOO[name]
    key = jax.random.PRNGKey(0)
    params = lw.init_params(zoo.graph, key, (IN_HW, IN_HW))
    acc = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
    # bits=6 keeps partial sums bit-exactness-safe (as throughput.py).
    cfg = PhotonicConfig(backend=Backend.HEANA, bits=6, dpe_size=83,
                         noise_enabled=False)
    engine = ServingEngine(params, acc, cfg, lowering=zoo.graph,
                           in_hw=IN_HW, max_batch=MAX_BATCH,
                           plan_cache=cache)
    cold = engine.warmup()
    mk = lambda i, n: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), (n, IN_HW, IN_HW, zoo.in_ch))

    # -- bucketed serving: concurrent warm max-bucket streams --------------
    full = [mk(100 + i, MAX_BATCH) for i in range(reps)]
    engine.infer(full[0])                       # warm the metrics path
    traces0 = trace_count()
    bucketed_ips = _stream_ips(engine, full, STREAM_THREADS)
    # -- mixed-size stream: padding overhead shows up in the stats ---------
    for i, n in enumerate((1, 3, MAX_BATCH)):
        engine.infer(mk(200 + i, n))
    retraces = trace_count() - traces0
    if retraces:
        failures.append(f"{name}: {retraces} retraces across warm bucket "
                        f"reuse — buckets were not pre-traced by warmup")

    # -- per-request baseline: batch-1 plan, one blocking call per image --
    plan1 = engine.plans[1]
    singles = [mk(300 + i, 1) for i in range(4 * reps)]
    execute_cnn(params, singles[0], plan1, cfg,
                lowering=zoo.graph).block_until_ready()    # warm
    t0 = time.perf_counter()
    for x1 in singles:
        execute_cnn(params, x1, plan1, cfg,
                    lowering=zoo.graph).block_until_ready()
    per_request_ips = len(singles) / (time.perf_counter() - t0)

    # -- data-parallel bit-identity (>= 2 devices) -------------------------
    n_dev = len(jax.devices())
    dp_bitexact: Optional[bool] = None
    dp_ips: Optional[float] = None
    if n_dev >= 2 and MAX_BATCH % n_dev == 0:
        dp = ServingEngine(params, acc, cfg, lowering=zoo.graph,
                           in_hw=IN_HW, max_batch=MAX_BATCH,
                           plan_cache=cache, data_parallel=True)
        dp.warmup()
        xb = full[0]
        dp_logits = dp.infer(xb)
        sd_logits = engine.infer(xb)
        dp_bitexact = bool(
            (jax.device_get(dp_logits) == jax.device_get(sd_logits)).all())
        if not dp_bitexact:
            failures.append(f"{name}: data-parallel logits != "
                            f"single-device logits ({n_dev} devices)")
        dp_ips = _stream_ips(dp, full, 1)

    stats = engine.stats()
    summary = serving_summary(
        name, MAX_BATCH, stats, bucketed_ips, per_request_ips,
        extras={"cold_s": cold, "dp_bitexact": dp_bitexact,
                "dp_ips": dp_ips, "retraces_warm": retraces,
                "in_hw": IN_HW, "stream_threads": STREAM_THREADS,
                "smoke": smoke, "bits": cfg.bits,
                "device": device_record()})
    return summary, failures


def measure(networks: Sequence[str] = NETWORKS, reps: int = 6,
            save: bool = True, smoke: bool = False,
            ) -> Tuple[List[Row], List[dict], List[str]]:
    """Returns (csv rows, summaries, hard-failure messages)."""
    cache = PlanCache()
    rows: List[Row] = []
    summaries: List[dict] = []
    failures: List[str] = []
    for name in networks:
        summary, fails = _measure_network(name, cache, reps, smoke)
        summaries.append(summary)
        failures.extend(fails)
        if save:
            save_summary(summary, EXP_DIR,
                         f"{name}_b{MAX_BATCH}_d{len(jax.devices())}.json")
        rows.append(Row(f"serving/{name}/bucketed_ips", 0.0,
                        round(summary["bucketed_ips"], 1)))
        rows.append(Row(f"serving/{name}/per_request_ips", 0.0,
                        round(summary["per_request_ips"], 1)))
        rows.append(Row(f"serving/{name}/speedup", 0.0,
                        round(summary["speedup"], 2)))
        rows.append(Row(f"serving/{name}/padding_fraction", 0.0,
                        round(summary["padding_fraction"], 3)))
        rows.append(Row(f"serving/{name}/retraces_warm", 0.0,
                        summary["retraces_warm"]))
        if summary["dp_bitexact"] is not None:
            rows.append(Row(f"serving/{name}/dp_bitexact", 0.0,
                            int(summary["dp_bitexact"])))
    no_retrace = all(s["retraces_warm"] == 0 for s in summaries)
    rows.append(Row("serving/no_retrace_warm", 0.0, int(no_retrace)))
    return rows, summaries, failures


def run() -> List[Row]:
    """benchmarks/run.py entry point (full grid + acceptance floor)."""
    rows, summaries, failures = measure(NETWORKS + FULL_EXTRA_NETWORKS)
    n_fast = sum(1 for s in summaries if s["name"] in NETWORKS
                 and s["speedup"] >= FULL_MIN_SPEEDUP)
    rows.append(Row("serving/ge_5x_on_two_networks", 0.0, int(n_fast >= 2)))
    if failures:
        raise RuntimeError("; ".join(failures))
    return rows


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced reps + CI assertions: zero warm "
                         "retraces, dp bit-identity (when >= 2 devices), "
                         "loose speedup floor; exits nonzero on breach")
    args = ap.parse_args(argv)
    reps = 3 if args.smoke else 6
    networks = (SMOKE_NETWORKS if args.smoke
                else NETWORKS + FULL_EXTRA_NETWORKS)
    rows, summaries, failures = measure(networks, reps=reps,
                                        save=not args.smoke,
                                        smoke=args.smoke)
    for r in rows:
        print(r.csv())
    status = 0
    checked = [s for s in summaries if s["name"] in NETWORKS]
    if args.smoke:
        for s in checked:
            if s["speedup"] < SMOKE_MIN_SPEEDUP:
                print(f"FAIL: {s['name']} bucketed/per-request speedup "
                      f"{s['speedup']:.2f}x < {SMOKE_MIN_SPEEDUP}x floor",
                      file=sys.stderr)
                status = 1
    else:
        n_fast = sum(1 for s in checked
                     if s["speedup"] >= FULL_MIN_SPEEDUP)
        if n_fast < 2:
            print(f"FAIL: only {n_fast} network(s) reached the "
                  f"{FULL_MIN_SPEEDUP}x bucketed/per-request floor "
                  f"(need >= 2): "
                  f"{[(s['name'], round(s['speedup'], 2)) for s in checked]}",
                  file=sys.stderr)
            status = 1
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
        status = 1
    if status == 0:
        print(f"serving: engine OK (zero warm retraces, speedups "
              f"{[round(s['speedup'], 1) for s in summaries]}, dp "
              f"bit-exact {[s['dp_bitexact'] for s in summaries]})")
    return status


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    raise SystemExit(main())
