"""One run of one cell: set-up, the measured window, the check, metrics.

``run.py`` is the command line over ``run_cell``; ``calibrate.py`` and
the tests call ``run_cell`` too.  Everything that belongs to one
configuration, traffic mix or metric is found by name: the
configuration in ``configs/<config>.json``, the mix in
``traffic/<traffic>.json``, each metric's reader in
``metrics/<metric>.py`` and each cell's correctness limit in
``limits/<cell>.json``.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import sys
import time
import types
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import inputs, network, reference, tracing, traffic, work

METRICS_DIR = os.path.join(network.BENCH_DIR, "metrics")
LIMITS_DIR = os.path.join(network.BENCH_DIR, "limits")
KERNELS_FILE = os.path.join(network.BENCH_DIR, "kernels.json")
BENCHMARK_FILE = os.path.join(network.REPO_DIR, "BENCHMARK.json")
#: The ``--trace 1`` run records the last seconds of its window.
TRACE_LENGTH_S = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_reader(metric: str) -> Callable:
    path = os.path.join(METRICS_DIR, f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones with
    ``--trace 0``, its per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


class CompileWatch:
    """Counts compilations and traces (JAX's monitoring events)."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} device(s) of platform {devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _key_fn(config: dict, seed: int):
    import jax
    if not config["operating_point"]["noise_enabled"]:
        return lambda i: None
    root = inputs.noise_root(seed)
    return lambda i: jax.random.fold_in(root, i)


def _warm(eng, mix: dict, data, key_fn) -> None:
    """Compile every shape this cell's traffic uses, and no other."""
    import jax.numpy as jnp
    if mix["kind"] == "closed":
        eng.infer(data[0], key=key_fn(0))
        return
    # The micro-batcher stacks 1..max_batch images, the engine pads the
    # stack to its bucket, and each request gets its row back.
    for n in range(1, int(mix["max_batch"]) + 1):
        stacked = jnp.stack([jnp.asarray(data[j]) for j in range(n)])
        out = eng.infer(stacked, key=key_fn(0))
        rows = [out[i] for i in range(n)]
        rows[-1].block_until_ready()


def _checked_batches(config: dict, mix: dict, win: traffic.Window,
                     data, seed: int) -> List[dict]:
    """The requests the check compares, drawn from the seed among those
    answered, each with the whole batch it was served in (activations
    are quantized per batch, so a row depends on its batch)."""
    done = [r.index for r in win.requests if r.index in win.outputs]
    if not done:
        return []
    rng = np.random.default_rng([seed, 6])
    pick = sorted(rng.choice(done, size=min(int(mix["check_requests"]),
                                            len(done)), replace=False))
    key_fn = _key_fn(config, seed)
    out = []
    for k in pick:
        req = win.requests[k]
        if mix["kind"] == "closed":
            x = data[win.image_of[k]]
            out.append({"request": int(k), "x": x, "rows": list(range(len(x))),
                        "key": key_fn(k),
                        "served": np.asarray(win.outputs[k])})
            continue
        batch = win.batches[req.batch]
        members = range(batch["first"], batch["first"] + batch["size"])
        x = np.stack([data[win.image_of[m]] for m in members])
        # The engine pads a batch with zero images to its bucket, the
        # power of two at or above it; so does the check.
        size = 1 << (len(x) - 1).bit_length()
        x = np.concatenate([x, np.zeros((size - len(x),) + x.shape[1:],
                                        x.dtype)])
        out.append({"request": int(k), "x": x,
                    "rows": [k - batch["first"]],
                    "key": key_fn(req.batch),
                    "served": np.asarray(win.outputs[k])[None]})
    return out


def row_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Relative L2 gap of each row of logits from the reference's row."""
    num = np.linalg.norm(served.astype(np.float64) - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref.astype(np.float64), axis=-1),
                     1e-30)
    return num / den


def check(config: dict, seed: int, batches: List[dict],
          control: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Runs the float32 reference over the checked batches: the gap of
    every served row from the reference's row ("program") and, with a
    ``control`` dtype, the gap of the reference computed in that
    precision ("control")."""
    import jax
    import jax.numpy as jnp
    params = inputs.weights(config, seed)
    ref = reference.jitted(config, jnp.float32)
    low = reference.jitted(config, getattr(jnp, control)) if control else None
    gaps: Dict[str, list] = {"program": [], "control": []}
    for b in batches:
        x = jnp.asarray(b["x"])
        want = np.asarray(ref(params, x, b["key"]))[b["rows"]]
        gaps["program"].extend(row_gaps(b["served"], want))
        if low is not None:
            got = np.asarray(low(params, x, b["key"]))[b["rows"]]
            gaps["control"].extend(row_gaps(got, want))
    del params
    jax.clear_caches()
    return {k: np.asarray(v) for k, v in gaps.items()}


#: How each compared number is read from the checked rows' gaps.
READINGS = {"logit_rel_err.max": np.max}


def judge(gaps: np.ndarray, limits: dict) -> Dict[str, dict]:
    """Each compared number beside its limit; no rows read as infinite."""
    return {name: {"value": float(READINGS[name](gaps)) if len(gaps)
                   else float("inf"), "limit": spec["limit"]}
            for name, spec in limits["checks"].items()}


def run_cell(cell: dict, config: dict, mix: dict, seed: int,
             seconds: float, trace: bool, *, bench: dict, limits: dict,
             t_process: float, require_tpu: bool = True,
             control: Optional[str] = None) -> dict:
    """One run; returns the result line (a dict) plus, under
    ``"_detail"``, what the caller may print before it.

    With ``control`` (a dtype name) the reference in that precision is
    put in the program's place: the check judges its rows, computed on
    the batches the window served, and the program's own readings go
    to ``_detail["program_checks"]``."""
    import jax
    from bench import program

    watch = CompileWatch()
    setup: Dict[str, float] = {}
    t = time.perf_counter()
    dev = device_info(int(cell["chips"]), require_tpu)
    setup["device_init_s"] = time.perf_counter() - t_process

    t = time.perf_counter()
    params = inputs.weights(config, seed)
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    if mix["kind"] == "closed":
        n = int(mix["request_images"])
        all_imgs = inputs.images(config, seed, n * int(mix["ring"]))
        data = [all_imgs[i * n:(i + 1) * n] for i in range(int(mix["ring"]))]
    else:
        data = inputs.images(config, seed, int(mix["image_pool"]))
    setup["images_s"] = time.perf_counter() - t

    t = time.perf_counter()
    eng = program.engine(config, params, int(mix["max_batch"]))
    setup["planning_s"] = time.perf_counter() - t

    key_fn = _key_fn(config, seed)
    t = time.perf_counter()
    c0, h0 = watch.compiles, watch.cache_hits
    _warm(eng, mix, data, key_fn)
    setup["compile_or_load_s"] = time.perf_counter() - t
    setup["compiles"] = watch.compiles - c0
    setup["cache_hits"] = watch.cache_hits - h0
    setup["compile_s"] = watch.compile_s

    capture = tracer = None
    if trace:
        capture = tracing.Capture()
        tracer = traffic.Tracer(capture,
                                max(seconds - TRACE_LENGTH_S, seconds / 2))
    compiles0, traces0 = watch.compiles, program.trace_count()
    setup["setup_s"] = time.perf_counter() - t_process
    if mix["kind"] == "closed":
        win = traffic.closed_loop(eng, mix, data, key_fn, seconds, tracer)
    else:
        root = (inputs.noise_root(seed)
                if config["operating_point"]["noise_enabled"] else None)
        win = traffic.poisson(eng, mix, data, seconds, seed,
                              program.MicroBatcher, key=root, tracer=tracer)
    in_window = {"compiles": watch.compiles - compiles0,
                 "traces": program.trace_count() - traces0}

    mem = memory_peak_bytes(int(cell["chips"]))
    bucket = (int(mix["request_images"]) if mix["kind"] == "closed"
              else int(mix["max_batch"]))
    sim = program.simulated(eng, bucket)
    batches = _checked_batches(config, mix, win, data, seed)
    failed = traffic.failed(win)
    never = sum(1 for r in win.requests if r.index not in win.outputs)
    win.outputs.clear()
    del eng, params
    gc.collect()
    jax.clear_caches()

    traced = None
    if trace:
        evs = tracing.events(capture.path, tracer.spans)
        capture.close()
        patterns = network.load_json(KERNELS_FILE)["taom"]
        red = tracing.reduce(evs, patterns)
        if not red["devices"] or not red["kernel_calls"]:
            raise SystemExit(
                f"bench: the trace has {red['devices']} device(s) with "
                f"operations on line {tracing.DEVICE_OPS_LINE!r} and "
                f"{red['kernel_calls']} kernel event(s) matching "
                f"{KERNELS_FILE}; the trace's layout is not the one "
                f"bench/tracing.py reads")
        on, off = tracer.on - win.start, tracer.off - win.start
        traced = dict(red, window_s=off - on, on_s=on, off_s=off,
                      top_ops=tracing.top_ops(evs),
                      idle_gaps=tracing.idle_gaps(evs))
        if mix["kind"] == "closed":
            inside = [r for r in win.requests
                      if r.sent >= on and r.done is not None
                      and r.done <= off]
            traced["calls"] = [r.images for r in inside]
        else:
            traced["batches"] = [b["size"] for b in win.batches
                                 if on <= (b["t0"] + b["t1"]) / 2 <= off]
        traced["images"] = sum(traced.get("calls") or
                               traced.get("batches") or [0])

    gaps = check(config, seed, batches, control)
    checks = judge(gaps["control" if control else "program"], limits)
    # A late answer is late, not wrong: ``failed`` counts it, the latency
    # counts its wait, and only an answer that never came is for the check.
    correct = (never == 0 and batches != [] and
               all(c["value"] <= c["limit"] for c in checks.values()))

    ctx = types.SimpleNamespace(
        cell=cell, config=config, mix=mix, window=win, setup=setup,
        traced=traced, gemms=network.logical_gemms(config),
        peak=work.peaks(dev["kind"]) if dev["platform"] == "tpu" else None,
        counters=win.counters)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=mem)
    result = {"correct": bool(correct), "attempted": len(win.requests),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["top_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["checks"] = checks
    late = win.late_s or [0.0]
    result["_detail"] = {
        "setup": setup, "in_window": in_window, "simulated": sim,
        "program_checks": judge(gaps["program"], limits),
        "row_gaps": [float(v) for v in gaps["program"]],
        "generator_late_ms": {"p50": 1e3 * traffic.percentile(late, 50),
                              "max": 1e3 * max(late)},
        "counters": win.counters,
        "traced": ({k: (len(v) if isinstance(v, list) else v)
                    for k, v in traced.items()
                    if k not in ("top_ops", "idle_gaps")}
                   if traced else None)}
    return result
