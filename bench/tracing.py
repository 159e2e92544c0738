"""Profiler capture and the reduction from a trace to device times.

A capture writes JAX's profiler trace (``.xplane.pb``) for a short
steady stretch of the window.  ``events`` reads it with nothing but
JAX's ``ProfileData`` into plain records, which the reductions below
take: the union of busy intervals, the time inside named kernels, and
the idle gaps with what the host was doing in each.  The reductions run
on records alone; the tests check them on hand-made events and on 60 ms
of a trace recorded on a TPU v5e
(``tests/data/chip-trace-poisson-b1.json.gz``).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

#: Planes and lines of a TPU trace that hold the device's operations.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_OPS_LINE = "XLA Ops"
#: The plane the benchmark's own host spans are put on.
HOST_PLANE = "/host:bench"

Event = Dict[str, object]       # plane, line, name, start_ns, dur_ns


class Capture:
    """``start``/``stop`` the profiler into a scratch directory under
    ``TMPDIR``, removed by ``close``."""

    def __init__(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.path: Optional[str] = None

    def start(self) -> None:
        import jax
        # Device operations only.  The runtime's host events (over a
        # million while one 64-image batch went to the chip) made a
        # blocking request take 0.67 s in place of 0.19 s on a v5e; the
        # benchmark notes its own host spans (``traffic.Tracer.span``).
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.dir}")
        self.path = found[-1]
        return self.path

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def events(path: str, spans: Iterable[Tuple[str, int, int]] = ()
           ) -> List[Event]:
    """Device operations of a trace, and the host ``spans`` (name,
    start and end in wall-clock ns) placed on the trace's clock."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    start_ns = None
    for plane in ProfileData.from_file(path).planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_ns = int(stats["profile_start_time"])
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns)})
    spans = list(spans)
    if spans and start_ns is None:
        raise RuntimeError(f"{path}: no profile_start_time to place the "
                           f"host spans by")
    for name, t0, t1 in spans:
        out.append({"plane": HOST_PLANE, "line": "spans", "name": name,
                    "start_ns": float(t0 - start_ns),
                    "dur_ns": float(t1 - t0)})
    return out


def device_events(evs: Iterable[Event]) -> List[Event]:
    return [e for e in evs if DEVICE_PLANE.match(str(e["plane"]))]


def host_spans(evs: Iterable[Event]) -> List[Event]:
    return [e for e in evs if not DEVICE_PLANE.match(str(e["plane"]))]


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _length(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _intervals(evs: Iterable[Event]) -> List[Tuple[float, float]]:
    return [(float(e["start_ns"]), float(e["start_ns"]) + float(e["dur_ns"]))
            for e in evs]


def is_kernel(name: str, patterns: Iterable[str]) -> bool:
    return any(re.search(p, name) for p in patterns)


def reduce(evs: List[Event], kernel_patterns: Iterable[str]) -> dict:
    """Device seconds of a trace, averaged over the devices in it.

    busy_s:    union of all operation intervals;
    kernel_s:  union of the intervals of operations whose name matches
               one of ``kernel_patterns``;
    glue_s:    busy time outside the kernels (busy_s - kernel_s);
    kernel_calls: how many kernel events there were (all devices).
    """
    dev = device_events(evs)
    planes = sorted({str(e["plane"]) for e in dev})
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "kernel_s": 0.0, "glue_s": 0.0,
                "kernel_calls": 0}
    pats = list(kernel_patterns)
    busy = kern = 0.0
    calls = 0
    for p in planes:
        mine = [e for e in dev if e["plane"] == p]
        iv = _intervals(mine)
        busy += _length(union(iv))
        k = [e for e in mine if is_kernel(str(e["name"]), pats)]
        calls += len(k)
        kern += _length(union(_intervals(k)))
    n = len(planes)
    return {"devices": n, "busy_s": busy / n * 1e-9,
            "kernel_s": kern / n * 1e-9,
            "glue_s": (busy - kern) / n * 1e-9, "kernel_calls": calls}


def top_ops(evs: List[Event], k: int = 10) -> List[List[object]]:
    """The ``k`` device operations that took most time, in seconds
    summed over their events (averaged over devices)."""
    dev = device_events(evs)
    n = max(1, len({str(e["plane"]) for e in dev}))
    tot: Dict[str, float] = {}
    for e in dev:
        tot[str(e["name"])] = tot.get(str(e["name"]), 0.0) + float(e["dur_ns"])
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n * 1e-9] for name, ns in ranked]


def idle_gaps(evs: List[Event], k: int = 10) -> List[List[object]]:
    """The ``k`` longest idle gaps of the first device, each named by the
    benchmark's host spans open at its midpoint ("host:none" where no
    span was open)."""
    dev = device_events(evs)
    if not dev:
        return []
    first = sorted({str(e["plane"]) for e in dev})[0]
    merged = union(_intervals(e for e in dev if e["plane"] == first))
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = host_spans(evs)
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        names = sorted({str(h["name"]) for h in spans
                        if float(h["start_ns"]) <= mid
                        <= float(h["start_ns"]) + float(h["dur_ns"])})
        out.append(["host:" + ("+".join(names) if names else "none"),
                    (e - s) * 1e-9])
    return out
