"""Batched multi-device serving engine over the compiled executor.

The executor's compiled hot path (exec.executor.compiled_forward) serves
one batch shape per plan: requests whose batch differs from ``plan.batch``
are rejected, and every new shape pays a trace.  Real CNN traffic arrives
in mixed sizes (see PAPERS.md, arXiv:2207.05278 — the system, not the
user, must map mixed-size tensors onto fixed hardware shapes), so this
module adds the serving layer HEANA's buffer-less "never stall" pitch
implies:

  * **batch buckets** — power-of-two batch sizes, each with its own
    ahead-of-time CnnPlan (scheduler.schedule_buckets on one shared plan
    cache).  An incoming request is zero-padded up to the smallest bucket
    that fits and the results are sliced back; requests larger than the
    top bucket are chunked.  Zero padding is numerics-neutral: the
    per-tensor quantize scale is a max over |activations| and the padded
    images stay zero through every layer, so the real rows' logits are
    bitwise what an exact-size batch would produce.  (Chunking is not:
    each chunk is its own batch, and the dynamic per-batch quantize scale
    means an over-max_batch request equals the concatenation of exact-size
    chunk runs — not one giant batch run.  The same holds for the
    micro-batcher: coalescing requests into one batch quantizes them
    together, so a coalesced request can differ from a solo run in the
    last quantization ULP — by design, exactly like batching on the real
    hardware's shared ADC range.);

  * **warmup()** — pre-traces every (bucket, sharding) executable with a
    dummy batch, so no serving request ever pays a trace (zero retraces
    after warmup is asserted by benchmarks/serving.py and CI);

  * a thread-safe **micro-batcher** — coalesces single-image requests
    from a queue into bucketed batches under a max-delay knob, reading
    each batch's logits to the host once and resolving each request's
    Future with its numpy row;

  * a **multi-device data-parallel path** — the bucketed batch is placed
    on a NamedSharding over the image batch axis of a 1-D ('data',) mesh
    (the spirit of parallel/sharding.py's batch_sharding) and the
    already-jitted forward is GSPMD-partitioned by XLA, except the Pallas
    kernels, which XLA cannot partition: each runs per device on its own
    rows (shard_map).  Because the contraction (K) axis is never sharded
    and the global quantize-scale max becomes an exact all-reduce max,
    the data-parallel logits are BITWISE equal to single-device
    execution when noise is off (benchmarks/serving.py checks this on 4
    virtual CPU devices, chip_smoke.py --chips 4 on four TPU chips);

  * **serving metrics** — always-on counters (mean service time and
    sustained throughput from the total time inside ``infer``, the time
    spent waiting for the device, the micro-batcher's queue wait and its
    host time per batch), padding-overhead fraction, the plan/compile
    cache stats surfaced from the existing ``stats()`` hooks, and the
    photonic model's energy accounting of the served stream (modeled
    joules per inference — padding included, that's the cost of
    bucketing — and sustained watts), derived from each bucket plan via
    core.hw.trace_energy.

Spans: with ``exec.spans.record(True)`` every call records the host
spans of the path (``engine.*`` in ``infer``; ``batcher.*`` on the
micro-batcher's worker, plus one ``batcher.queue_wait`` per request) on
the wall clock a profiler trace is placed by; ``exec.spans.drain()``
returns them.  The counters above come from the same clock reads.

Noise: a noise-enabled engine requires a root PRNG key per ``infer`` call
(per-chunk keys are folded in, per-layer keys inside the forward).  The
data-parallel path is noise-off only — per-shard noise streams would
diverge from the single-device stream, silently breaking reproducibility.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hw
from repro.core.types import PhotonicConfig
from repro.exec import executor as ex
from repro.exec import plan_cache as pc
from repro.exec import spans
from repro.exec.scheduler import CnnPlan, HardwareSpec, schedule_buckets
from repro.models import cnn as cnn_mod

__all__ = ["ServingEngine", "MicroBatcher", "power_of_two_buckets",
           "bucket_for"]


def power_of_two_buckets(max_batch: int) -> Tuple[int, ...]:
    """(1, 2, 4, ..., max_batch) with max_batch rounded UP to a power
    of two — a request never lands in a smaller bucket than itself."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets: List[int] = [1]
    while buckets[-1] < max_batch:
        buckets.append(buckets[-1] * 2)
    return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (buckets ascending; n must fit the largest)."""
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"batch {n} exceeds the largest bucket "
                     f"{buckets[-1]} — the engine chunks before bucketing, "
                     f"so this is an internal error")


class ServingEngine:
    """Bucketed, warmed-up, optionally data-parallel CNN serving.

    One engine serves one network (lowering + params) on one accelerator
    config.  All entry points are thread-safe: concurrent request threads
    share the pre-traced executables and serialize only on metrics
    bookkeeping (the forward itself runs outside any lock).

    Parameters
    ----------
    params, acc, cfg : the executor's usual weight dict, the hardware
        (an AcceleratorConfig, or — preferred — a core.hw.OperatingPoint,
        in which case ``cfg`` may be omitted and is derived coherently
        via ``op.kernel_config()``), and the PhotonicConfig numerics.
        A ``cfg`` whose bits/DPE geometry disagrees with the plans'
        hardware is rejected HERE, at construction — not after the first
        mis-modeled request.
    lowering : op-graph / legacy tuple; default small CNN.
    in_hw : input spatial size (int or (H, W)).
    max_batch : largest bucket (rounded up to a power of two).  Larger
        requests are chunked into top-bucket pieces.
    data_parallel : shard bucketed batches over ``devices`` (default
        ``jax.devices()``) via NamedSharding on the batch axis.  Buckets
        not divisible by the device count fall back to single-device.
        Requires cfg.noise_enabled=False.
    plan_cache : shared PlanCache (fresh one per engine by default).
    """

    def __init__(self, params: dict, acc: HardwareSpec,
                 cfg: Optional[PhotonicConfig] = None, lowering=None,
                 in_hw=16, max_batch: int = 32, impl: str = "auto",
                 objective: str = "latency",
                 plan_cache: Optional[pc.PlanCache] = None,
                 data_parallel: bool = False,
                 devices: Optional[Sequence] = None) -> None:
        if cfg is None:
            if not isinstance(acc, hw.OperatingPoint):
                raise ValueError(
                    "cfg is required when acc is a bare AcceleratorConfig "
                    "— pass a PhotonicConfig, or hand the engine a "
                    "core.hw.OperatingPoint and let it derive the kernel "
                    "config coherently (op.kernel_config())")
            cfg = acc.kernel_config()
        self._params = params
        self._cfg = cfg
        self._impl = impl
        self._lowering = ex._norm_lowering(lowering)
        self._in_hw = ((in_hw, in_hw) if isinstance(in_hw, int)
                       else (int(in_hw[0]), int(in_hw[1])))
        self._in_ch = cnn_mod.as_graph(self._lowering,
                                       params=params).input.cout
        self.buckets = power_of_two_buckets(max_batch)
        self.plan_cache = (plan_cache if plan_cache is not None
                           else pc.PlanCache())
        gemms = cnn_mod.lowered_gemms(params, self._lowering, self._in_hw)
        self.plans: Dict[int, CnnPlan] = schedule_buckets(
            gemms, acc, self.buckets, objective, cache=self.plan_cache)
        # Fail fast on incoherent hardware: every bucket shares one
        # hardware spec, so checking any plan pins cfg against all of
        # them.  (The executor re-checks per request via _validate — this
        # just moves the clear error to construction time.)
        hw.check_kernel_plan_coherence(cfg, self.plans[self.buckets[0]])

        self.devices = (list(devices) if devices is not None
                        else list(jax.devices()))
        self.data_parallel = bool(data_parallel) and len(self.devices) > 1
        if bool(data_parallel) and cfg.noise_enabled:
            raise ValueError(
                "data_parallel serving requires noise_enabled=False — "
                "per-shard noise streams would not reproduce the "
                "single-device stream (run noisy inference single-device)")
        self._mesh = None
        if self.data_parallel:
            self._mesh = Mesh(np.asarray(self.devices), ("data",))
            self._x_sharding = NamedSharding(self._mesh,
                                             P("data", None, None, None))
            self._params_dp = jax.device_put(
                params, NamedSharding(self._mesh, P()))
        # One compiled wrapper per bucket, built up front: the jit
        # executables themselves materialize at warmup()/first call.
        self._fns = {b: ex.compiled_forward(
            self.plans[b], cfg, self._lowering, impl,
            mesh=self._mesh if self._dp_bucket(b) else None)
            for b in self.buckets}

        self._lock = threading.Lock()
        self._requests = 0
        self._images = 0
        self._blocked_requests = 0
        self._blocked_images = 0
        self._batches = 0
        self._padded_slots = 0
        self._executed_slots = 0
        self._infer_ns = 0
        self._device_wait_ns = 0
        # Each thread's last blocking infer's device wait, for the
        # micro-batcher's host time per batch.
        self._local = threading.local()
        self._warm = False
        self._retraces = 0
        # Modeled photonic energy of the executed stream: per-bucket
        # joules/latency are precomputed once from the plans (core.hw
        # executed-trace accounting) and accumulated per executed batch —
        # padding slots burn real energy, so a padded bucket is charged
        # in full (the padding overhead is visible in j_per_image).
        self._bucket_energy = {b: hw.trace_energy(self.plans[b])
                               for b in self.buckets}
        self._energy_j = 0.0
        self._model_time_s = 0.0

    # -- bucket plumbing -----------------------------------------------------
    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def _dp_bucket(self, bucket: int) -> bool:
        return self.data_parallel and bucket % len(self.devices) == 0

    def _run_bucket(self, xb: jnp.ndarray, key, bucket: int) -> jnp.ndarray:
        fn = self._fns[bucket]
        traces0 = ex.trace_count() if self._warm else 0
        if self._dp_bucket(bucket):
            xb = jax.device_put(xb, self._x_sharding)
            logits, _, _ = fn(self._params_dp, xb, key)
        else:
            logits, _, _ = fn(self._params, xb, key)
        if self._warm:
            # Engine-local retrace accounting: tally only traces that
            # happened across THIS engine's calls — another engine's
            # warmup elsewhere in the process must not show up here.
            traced = ex.trace_count() - traces0
            if traced:
                with self._lock:
                    self._retraces += traced
        return logits

    def _infer_chunk(self, x: jnp.ndarray, start: int, n: int,
                     key) -> jnp.ndarray:
        bucket = bucket_for(n, self.buckets)
        pad = bucket - n
        with spans.span("engine.pad"):
            chunk = x[start:start + n]
            xb = (chunk if pad == 0 else jnp.concatenate(
                [chunk, jnp.zeros((pad,) + chunk.shape[1:], chunk.dtype)]))
        # The executor's own eager validation surfaces its clear errors
        # (geometry mismatch, noise-without-key) through the serving
        # entry point, before anything touches the compiled path.
        with spans.span("engine.validate"):
            ex._validate(xb, self.plans[bucket], self._cfg, self._lowering,
                         key)
        with spans.span("engine.dispatch"):
            logits = self._run_bucket(xb, key, bucket)
        te = self._bucket_energy[bucket]
        with self._lock:
            self._batches += 1
            self._padded_slots += pad
            self._executed_slots += bucket
            self._energy_j += te.energy_j
            self._model_time_s += te.latency_s
        with spans.span("engine.slice"):
            return logits[:n] if pad else logits

    # -- public entry points -------------------------------------------------
    def warmup(self, key: Optional[jax.Array] = None) -> Dict[int, float]:
        """Pre-trace every (bucket, sharding) executable with a dummy
        batch so no serving request ever pays a trace.  Returns
        {bucket: cold_seconds}.  With noise enabled a dummy root key is
        used — serving keys reuse the same executable (same key shape).
        """
        if key is None and self._cfg.noise_enabled:
            key = jax.random.PRNGKey(0)
        if not self._cfg.noise_enabled:
            key = None
        h, w = self._in_hw
        cold: Dict[int, float] = {}
        for b in self.buckets:
            x = jnp.zeros((b, h, w, self._in_ch), jnp.float32)
            t0 = time.perf_counter()
            self._run_bucket(x, key, b).block_until_ready()
            cold[b] = time.perf_counter() - t0
        with self._lock:
            self._warm = True
            self._retraces = 0
        return cold

    def infer(self, x, key: Optional[jax.Array] = None,
              block: bool = True) -> jnp.ndarray:
        """Serve one request: (N, H, W, C) images -> (N, classes) logits.

        N is arbitrary: it is padded up to the smallest bucket that fits
        (chunked into top-bucket pieces first if N > max_bucket; with a
        key, each chunk folds in its index so chunk noise stays
        independent).  ``block=True`` (default) waits for the device so
        the recorded latency is true request latency; ``block=False``
        returns the dispatched arrays immediately — such calls still
        count toward request/image/padding totals but are EXCLUDED from
        ``infer_s_total`` and the figures derived from it (a
        dispatch-only duration is not a request latency).
        """
        self._local.device_wait_ns = 0
        with spans.span("engine.infer") as whole:
            logits = self._infer(x, key, block)
        wait_ns = self._local.device_wait_ns
        n = logits.shape[0]
        with self._lock:
            self._requests += 1
            self._images += n
            if block:
                self._blocked_requests += 1
                self._blocked_images += n
                self._infer_ns += whole.t1 - whole.t0
                self._device_wait_ns += wait_ns
        return logits

    def _infer(self, x, key: Optional[jax.Array],
               block: bool) -> jnp.ndarray:
        x = jnp.asarray(x)
        if x.ndim != 4:
            raise ValueError(f"x must be (N, H, W, C) images, got shape "
                             f"{tuple(x.shape)} — for a single image use "
                             f"infer_one or x[None]")
        n = x.shape[0]
        if n == 0:
            raise ValueError("empty request: x has batch 0")
        if not self._cfg.noise_enabled:
            key = None          # keep one executable per bucket
        outs: List[jnp.ndarray] = []
        start, ci = 0, 0
        n_chunks = -(-n // self.max_bucket)
        while start < n:
            take = min(self.max_bucket, n - start)
            ck = (jax.random.fold_in(key, ci)
                  if key is not None and n_chunks > 1 else key)
            outs.append(self._infer_chunk(x, start, take, ck))
            start += take
            ci += 1
        if len(outs) == 1:
            logits = outs[0]
        else:
            with spans.span("engine.slice"):
                logits = jnp.concatenate(outs)
        if block:
            with spans.span("engine.device_wait") as wait:
                logits.block_until_ready()
            self._local.device_wait_ns = wait.t1 - wait.t0
        return logits

    def infer_one(self, image, key: Optional[jax.Array] = None
                  ) -> jnp.ndarray:
        """Serve a single (H, W, C) image -> (classes,) logits."""
        image = jnp.asarray(image)
        if image.ndim != 3:
            raise ValueError(f"image must be (H, W, C), got shape "
                             f"{tuple(image.shape)}")
        return self.infer(image[None], key=key)[0]

    def stats(self) -> dict:
        """Serving metrics + the underlying cache/trace hooks."""
        with self._lock:
            infer_s = self._infer_ns * 1e-9
            warm = self._warm
            retraces = self._retraces
            out = {
                "requests": self._requests,
                "images": self._images,
                "batches": self._batches,
                "padded_slots": self._padded_slots,
                "executed_slots": self._executed_slots,
                "padding_fraction": (
                    self._padded_slots / self._executed_slots
                    if self._executed_slots else 0.0),
                # Service time of blocking calls, from the call to its
                # logits: no queue wait (MicroBatcher.stats() has it).
                "infer_s_total": infer_s,
                "device_wait_s_total": self._device_wait_ns * 1e-9,
                "latency_mean_s": (infer_s / self._blocked_requests
                                   if self._blocked_requests else 0.0),
                "sustained_ips": (self._blocked_images / infer_s
                                  if infer_s > 0 else 0.0),
                "buckets": list(self.buckets),
                "data_parallel": self.data_parallel,
                "n_devices": len(self.devices),
                "warmed_up": warm,
                # Photonic-model energy of the served stream (NOT host
                # wall-clock electricity): joules per *real* inference —
                # padding overhead included, that's the serving cost of
                # bucketing — and the accelerator's sustained draw over
                # the modeled busy time.
                "modeled_energy_j": self._energy_j,
                "modeled_j_per_image": (self._energy_j / self._images
                                        if self._images else 0.0),
                "modeled_sustained_w": (self._energy_j / self._model_time_s
                                        if self._model_time_s > 0 else 0.0),
            }
        out["retraces_since_warmup"] = retraces if warm else None
        out["plan_cache"] = self.plan_cache.stats()
        out["compile_cache"] = ex.compile_cache_stats()
        return out


class _Queued(NamedTuple):
    image: jnp.ndarray      # (1, H, W, C), on the device
    fut: Future
    request: int            # id, in submit order
    submit_ns: int          # wall clock at the enqueue


class MicroBatcher:
    """Thread-safe request coalescer: single images in, bucketed batches
    through a ServingEngine, per-request Futures out.

    A background worker takes the first queued request, then keeps
    gathering until either ``max_batch`` requests are in hand or
    ``max_delay_s`` has elapsed since the first one — the classic
    latency/throughput knob.  ``submit`` puts each image on the device
    as a (1, H, W, C) array, so the worker assembles a batch with one
    concatenation; the batch goes through ``engine.infer`` (which pads
    it to a bucket), its logits are read to the host once, and each
    Future resolves with its own row: a read-only (classes,) numpy
    array, bitwise the device's logits.  The worker's device work per
    batch is thus constant, not one operation per request.

    With a noise-enabled engine pass a root ``key``: each formed batch
    folds in a monotonic counter, so batches draw independent noise and
    a given (key, arrival order) replays exactly.

    ``stats()`` counts, over the batches formed, their fill, each
    request's wait in the queue (submit to its batch's dispatch) and the
    time the worker spent on each batch, split into waiting for the
    device and host work (assembly, the engine's host path, the read of
    the logits and resolving the Futures); ``result_reads`` counts the
    batches resolved from one device-to-host read and
    ``result_read_s_total`` the time those reads took.
    """

    def __init__(self, engine: ServingEngine, max_delay_s: float = 0.002,
                 max_batch: Optional[int] = None,
                 key: Optional[jax.Array] = None) -> None:
        if max_delay_s < 0:
            raise ValueError(f"max_delay_s must be >= 0, got {max_delay_s}")
        self._engine = engine
        self._max_delay_s = float(max_delay_s)
        self._max_batch = int(max_batch or engine.max_bucket)
        if self._max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if engine._cfg.noise_enabled and key is None:
            raise ValueError(
                "engine has noise_enabled=True: MicroBatcher needs a root "
                "PRNG key (per-batch keys are folded in)")
        self._key = key
        self._batch_counter = 0
        self._queue: "queue.Queue[_Queued]" = queue.Queue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # Ids are taken with the enqueue under one lock, so the queue
        # holds them in order and a batch holds consecutive ones.
        self._submit_lock = threading.Lock()
        self._request_ids = itertools.count()
        self._batch_ids = itertools.count()
        self._batches_formed = 0
        self._requests_batched = 0
        self._queue_wait_ns = 0
        self._queue_wait_max_ns = 0
        self._batch_host_ns = 0
        self._batch_device_wait_ns = 0
        self._result_reads = 0
        self._result_read_ns = 0

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "MicroBatcher":
        if self._thread is not None:
            raise RuntimeError("MicroBatcher already started")
        self._thread = threading.Thread(target=self._run,
                                        name="micro-batcher", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        """Stop the worker after draining already-queued requests.

        A submit() that passed its stopped-check concurrently with this
        call may enqueue after the worker exits; the drain below picks
        such stragglers up so no accepted Future is left unresolved.
        """
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        self._drain_now()

    def __enter__(self) -> "MicroBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- request path --------------------------------------------------------
    def submit(self, image) -> "Future":
        """Enqueue one (H, W, C) image; the Future resolves to its
        (classes,) logits as a numpy row (or raises what the engine
        raised).  A host image is reshaped to (1, H, W, C) on the host
        and sent to the device here, on the caller's thread."""
        if self._stop.is_set():
            raise RuntimeError("MicroBatcher is stopped")
        on_device = isinstance(image, jax.Array)
        if not on_device:
            image = np.asarray(image)
        if image.ndim != 3:
            raise ValueError(f"image must be (H, W, C), got shape "
                             f"{tuple(image.shape)}")
        image = image[None] if on_device else jnp.asarray(image[None])
        fut: Future = Future()
        with self._submit_lock:
            self._queue.put(_Queued(image, fut, next(self._request_ids),
                                    time.time_ns()))
        return fut

    def _next_key(self):
        if self._key is None:
            return None
        k = jax.random.fold_in(self._key, self._batch_counter)
        self._batch_counter += 1
        return k

    def _drain_now(self) -> None:
        """Dispatch everything currently queued, in bucket-size groups
        (queue.get is atomic, so a concurrent worker and a draining
        stop() cannot double-dispatch a request)."""
        while True:
            group: list = []
            while len(group) < self._max_batch:
                try:
                    group.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            if not group:
                return
            self._dispatch(group, next(self._batch_ids))

    def _run(self) -> None:
        while True:
            try:
                first = self._queue.get(timeout=0.02)
            except queue.Empty:
                if self._stop.is_set():
                    self._drain_now()      # requests that raced the stop
                    return
                continue
            bid = next(self._batch_ids)
            batch = [first]
            with spans.span("batcher.coalesce", batch=bid):
                deadline = time.perf_counter() + self._max_delay_s
                while len(batch) < self._max_batch:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._queue.get(timeout=remaining))
                    except queue.Empty:
                        break
            self._dispatch(batch, bid)

    def _dispatch(self, batch: List[_Queued], bid: int) -> None:
        with spans.span("batcher.batch", batch=bid,
                        request=batch[0].request, count=len(batch)) as whole:
            waits = [whole.t0 - r.submit_ns for r in batch]
            for r in batch:
                spans.add("batcher.queue_wait", r.submit_ns, whole.t0,
                          request=r.request, batch=bid)
            try:
                # Assembly and the read are inside the guard: mixed image
                # shapes in one coalescing window, or a failed read, must
                # fail THESE futures, not kill the worker thread (which
                # would hang every later request).
                with spans.span("batcher.stack"):
                    shapes = {r.image.shape for r in batch}
                    if len(shapes) > 1:
                        raise ValueError(
                            f"images of different shapes in one batch: "
                            f"{sorted(sh[1:] for sh in shapes)}")
                    images = jnp.concatenate([r.image for r in batch])
                logits = self._engine.infer(images, key=self._next_key())
                with spans.span("batcher.scatter"):
                    with spans.span("batcher.read") as read:
                        rows = np.asarray(logits)
                    for r, row in zip(batch, rows):
                        r.fut.set_result(row)
            except Exception as exc:  # surface engine errors per request
                for r in batch:
                    if not r.fut.done():
                        r.fut.set_exception(exc)
                return
            wait_ns = self._engine._local.device_wait_ns
        with self._lock:
            self._batches_formed += 1
            self._requests_batched += len(batch)
            self._queue_wait_ns += sum(waits)
            self._queue_wait_max_ns = max(self._queue_wait_max_ns,
                                          max(waits))
            self._batch_host_ns += whole.t1 - whole.t0 - wait_ns
            self._batch_device_wait_ns += wait_ns
            self._result_reads += 1
            self._result_read_ns += read.t1 - read.t0

    def stats(self) -> dict:
        with self._lock:
            formed = self._batches_formed
            n = self._requests_batched
            waits = {"queue_wait_s_total": self._queue_wait_ns * 1e-9,
                     "queue_wait_s_max": self._queue_wait_max_ns * 1e-9,
                     "batch_host_s_total": self._batch_host_ns * 1e-9,
                     "batch_device_wait_s_total":
                         self._batch_device_wait_ns * 1e-9,
                     "result_reads": self._result_reads,
                     "result_read_s_total": self._result_read_ns * 1e-9}
        return {"batches_formed": formed, "requests_batched": n,
                "mean_fill": (n / formed) if formed else 0.0, **waits,
                "max_delay_s": self._max_delay_s,
                "max_batch": self._max_batch,
                "queued": self._queue.qsize()}
