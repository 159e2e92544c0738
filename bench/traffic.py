"""The one traffic generator; a mix is a JSON file of its parameters.

Two kinds of mix, chosen by the file's ``kind``:

``closed``  One client calls ``engine.infer(x, key)`` back to back and
            waits for each answer.  Request i sends batch i mod ``ring``
            of ``ring`` distinct host batches of ``request_images``.
            The window runs until the first request that completes at or
            after ``seconds``; its length is the time to that completion.

``poisson`` Single-image requests arrive open-loop at ``rate_per_s``
            through ``MicroBatcher(engine, max_delay_s)``.  Every seed
            gets the same set of arrival gaps (the quantiles of an
            exponential law, scaled so the last request is due at
            ``seconds``) in an order drawn from the seed, and the images
            from a pool of ``image_pool`` distinct ones, so the work is
            fixed and the seed only reorders it.  The window closes
            ``drain_s`` after the last arrival; a request not answered
            by then counts as failed.  Each is still waited for, up to
            ``ANSWER_WAIT_S`` past the close: its latency runs from its
            due time to its answer, late or not, and one never answered
            is for the check.

A ``Tracer`` may be handed in: the drivers call ``tick`` between
requests, which starts the profiler at a request boundary, and
``finish`` after the last request, which stops it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

#: How long past an open-loop window's close its requests are waited for.
ANSWER_WAIT_S = 60.0


@dataclasses.dataclass
class Request:
    index: int
    images: int
    due: float            # seconds from the window's start
    sent: float
    done: Optional[float] = None
    batch: int = -1       # closed: its own index; poisson: the batch


@dataclasses.dataclass
class Window:
    requests: List[Request]
    outputs: Dict[int, object]      # closed: request -> logits;
                                    # poisson: request -> logits row
    start: float                    # perf_counter at the window's start
    length_s: float
    batches: List[dict]             # poisson: {size, first, t0, t1}
    late_s: List[float]             # poisson: how late each send was
    counters: Dict[str, dict]       # the program's own stats() after it
    image_of: List[int]             # request -> its image (poisson) or
                                    # ring batch (closed)


class Tracer:
    """Starts the profiler at the first request boundary after
    ``start_s`` into the window; ``finish``, called once the last
    request is sent (open loop) or answered (closed loop), stops it.
    ``on``/``off`` hold the host times.  While it records, ``span``
    notes the benchmark's own host spans on the wall clock, which the
    profiler's trace is on too (``tracing.events`` puts them in it)."""

    def __init__(self, capture, start_s: float) -> None:
        self.capture = capture
        self.start_s = start_s
        self.on: Optional[float] = None
        self.off: Optional[float] = None
        self.spans: List[Tuple[str, int, int]] = []
        self._recording = False

    def tick(self, elapsed: float) -> None:
        if self.on is None and elapsed >= self.start_s:
            self.capture.start()
            self._recording = True
            self.on = time.perf_counter()

    def finish(self) -> None:
        if self.on is not None and self.off is None:
            self._recording = False
            self.off = time.perf_counter()
            self.capture.stop()

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.time_ns() if self._recording else None
        yield
        if t is not None:
            self.spans.append((name, t, time.time_ns()))


def _span(tracer: Optional[Tracer], name: str):
    return (tracer.span(name) if tracer is not None
            else contextlib.nullcontext())


def closed_loop(engine, mix: dict, ring: List[np.ndarray],
                key_fn: Callable[[int], object], seconds: float,
                tracer: Optional[Tracer] = None) -> Window:
    reqs: List[Request] = []
    outputs: Dict[int, object] = {}
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.tick(now - t0)
        with _span(tracer, "bench.between"):
            x = ring[i % len(ring)]
            key = key_fn(i)
        sent = time.perf_counter() - t0
        with _span(tracer, "bench.infer"):
            out = engine.infer(x, key=key)
        done = time.perf_counter() - t0
        reqs.append(Request(i, x.shape[0], sent, sent, done, i))
        outputs[i] = out
        i += 1
        if done >= seconds:
            break
    if tracer is not None:
        tracer.finish()
    return Window(reqs, outputs, t0, reqs[-1].done, [], [],
                  {"engine": engine.stats()},
                  [r.index % len(ring) for r in reqs])


class _Recorder:
    """Stands in front of the engine for the micro-batcher and notes
    each batch it forms (its size and times); the batcher's queue is
    FIFO, so batch b holds the next ``size`` requests in send order."""

    def __init__(self, engine, t0: float, tracer: Optional[Tracer]) -> None:
        self._engine = engine
        self._t0 = t0
        self._tracer = tracer
        self.batches: List[dict] = []
        self._next = 0

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def infer(self, images, key=None):
        n = images.shape[0]
        t1 = time.perf_counter()
        with _span(self._tracer, "bench.batch"):
            out = self._engine.infer(images, key=key)
        self.batches.append({"size": n, "first": self._next,
                             "t0": t1 - self._t0,
                             "t1": time.perf_counter() - self._t0})
        self._next += n
        return out


def arrival_gaps(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """The gaps between due times: the same set for every seed."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    q = -np.log1p(-(np.arange(n) + 0.5) / n)       # exponential quantiles
    q *= seconds / q.sum()
    return np.random.default_rng([seed, 4]).permutation(q)


def poisson(engine, mix: dict, pool: np.ndarray, seconds: float, seed: int,
            batcher_cls, key=None, tracer: Optional[Tracer] = None
            ) -> Window:
    gaps = arrival_gaps(mix, seconds, seed)
    due = np.cumsum(gaps)
    order = np.random.default_rng([seed, 5]).integers(0, len(pool),
                                                     len(due))
    t0 = time.perf_counter()
    rec = _Recorder(engine, t0, tracer)
    batcher = batcher_cls(rec, max_delay_s=float(mix["max_delay_s"]),
                          key=key)
    reqs: List[Request] = []
    futs = []
    late: List[float] = []
    lock = threading.Lock()

    def _done(k: int):
        def cb(_fut):
            t = time.perf_counter() - t0
            with lock:
                reqs[k].done = t
        return cb

    batcher.start()
    try:
        t0 = time.perf_counter()
        rec._t0 = t0
        for k, d in enumerate(due):
            now = time.perf_counter()
            if tracer is not None:
                tracer.tick(now - t0)
            wait = d - (now - t0)
            if wait > 0:
                with _span(tracer, "bench.sleep"):
                    time.sleep(wait)
            sent = time.perf_counter() - t0
            late.append(max(0.0, sent - d))
            with lock:
                reqs.append(Request(k, 1, float(d), sent))
            with _span(tracer, "bench.submit"):
                fut = batcher.submit(pool[order[k]])
            fut.add_done_callback(_done(k))
            futs.append(fut)
        if tracer is not None:
            tracer.finish()
        close = seconds + float(mix["drain_s"])
        deadline = t0 + close + ANSWER_WAIT_S
        for fut in futs:
            try:
                fut.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:   # noqa: BLE001 - never answered: checked
                pass
    finally:
        batcher.stop(timeout=ANSWER_WAIT_S)
    outputs = {k: f.result() for k, f in enumerate(futs)
               if f.done() and f.exception() is None}
    for b, batch in enumerate(rec.batches):
        for k in range(batch["first"], batch["first"] + batch["size"]):
            reqs[k].batch = b
    return Window(reqs, outputs, t0, close, rec.batches, late,
                  {"engine": engine.stats(), "batcher": batcher.stats()},
                  [int(j) for j in order])


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of the values."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]


def failed(win: Window) -> int:
    """Requests not answered by the window's close."""
    return sum(1 for r in win.requests
               if r.done is None or r.done > win.length_s)


def latencies_ms(win: Window) -> List[float]:
    """Each request's latency in ms, from its due time to its answer; one
    never answered counts as waiting until the wait ran out."""
    give_up = win.length_s + ANSWER_WAIT_S
    return [1e3 * ((r.done if r.done is not None else give_up) - r.due)
            for r in win.requests]
