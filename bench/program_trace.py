"""A traced run of one cell with the program's own spans and node scopes.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--trace 1] [--out FILE]

Runs the cell as ``run.py`` does, with the program's span recording
(``repro.exec.spans``) on for the whole run, and prints what ``run.py``
prints.  With ``--trace 1`` the program's spans join the benchmark's on
the trace (so ``breakdown.idle_gaps`` names what the micro-batcher's
worker was doing), each device operation gets the node whose named
scope its ``op_name`` carries (read from the HLO protos the profiler
keeps beside the trace), and a last line ``program_trace`` gives:
the host split of the worker per batch traced, the median queue wait of
the requests of those batches, the nodes that took most device time,
and how much of the busy time and kernel time the node scopes cover.
``--out`` (gzipped JSON) keeps those with the raw spans and, per
distinct device operation, its ``op_name``.  With ``--trace 0`` the run
measures what recording costs against ``run.py``.

The functions work on ``tracing``'s records and are checked in
``tests/test_program_trace.py``.  They sit here, not in ``harness.py``
and ``tracing.py``, until a benchmark change calls them from every
``--trace 1`` run (PERF.md, Open questions).
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import re
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import tracing  # noqa: E402

#: A device operation's own stat that holds its ``op_name``, where the
#: trace keeps one (the HLO protos of the metadata plane always do).
OP_NAME_STAT = "tf_op"
#: Children of ``batcher.batch`` and of ``engine.infer`` in the split.
BATCH_PARTS = ("batcher.stack", "engine.validate", "engine.pad",
               "engine.dispatch", "engine.slice", "engine.device_wait",
               "batcher.scatter")
_INSTRUCTION = re.compile(r"^(?:ROOT )?(%[^ ]+) = (.*?) [a-z][a-z0-9-]*\(")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_of(op_name: str, nodes: Iterable[str]) -> Optional[str]:
    """The node whose named scope ``op_name`` (a ``/`` path) is under."""
    nodes = nodes if isinstance(nodes, (set, frozenset, dict)) else set(nodes)
    for part in op_name.split("/"):
        if part in nodes:
            return part
    return None


def op_key(text: str) -> Optional[Tuple[str, str]]:
    """(instruction name, result type without layouts) of an HLO
    instruction's text, as a trace names a device operation."""
    m = _INSTRUCTION.match(text.strip())
    if not m:
        return None
    return m.group(1), re.sub(r"\{[^{}]*\}|/\*[^*]*\*/", "", m.group(2))


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) pairs of a protobuf message: ints for
    varint and fixed-width fields, bytes for length-delimited ones."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = int.from_bytes(b[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _event_metadata(path: str):
    """(plane name, [(name, display name, {stat: value})]) of each plane
    of an ``.xplane.pb`` (XSpace): the event metadata that
    ``ProfileData`` does not expose."""
    with open(path, "rb") as f:
        space = f.read()
    for field, plane in _fields(space):
        if field != 1:                          # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for g, v in _fields(plane):
            if g == 2:                          # XPlane.name
                name = v.decode()
            elif g in (4, 5):                   # event_/stat_metadata maps
                entry = dict(_fields(v))
                if g == 4:
                    metas.append(entry.get(2, b""))
                else:
                    stat_names[entry.get(1, 0)] = dict(
                        _fields(entry.get(2, b""))).get(2, b"").decode()
        out = []
        for raw in metas:
            md = {1: b"", 2: b"", 4: b""}
            stats = {}
            for h, w in _fields(raw):
                if h == 5:                      # XEventMetadata.stats
                    st = dict(_fields(w))
                    value = (st[5].decode(errors="replace") if 5 in st
                             else stat_names.get(st[7], "") if 7 in st
                             else st.get(6, st.get(3, st.get(4))))
                    stats[stat_names.get(st.get(1), "")] = value
                else:
                    md[h] = w
            out.append((md[2].decode(errors="replace"),
                        md[4].decode(errors="replace"), stats))
        yield name, out


def op_names(path: str) -> Dict[Tuple[str, str], str]:
    """The ``op_name`` of each HLO instruction the trace's programs hold,
    by ``op_key``: from the HLO protos on the profiler's metadata plane,
    and from a device operation's own ``OP_NAME_STAT`` where present."""
    from jax._src.lib import xla_client
    hlo_module = xla_client._xla.HloModule
    out: Dict[Tuple[str, str], str] = {}
    for plane, metas in _event_metadata(path):
        for name, display, stats in metas:
            if isinstance(stats.get("Hlo Proto"), bytes):
                module = dict(_fields(stats["Hlo Proto"])).get(1)
                text = hlo_module.from_serialized_hlo_module_proto(
                    module).to_string()
                for line in text.splitlines():
                    k, m = op_key(line), _OP_NAME.search(line)
                    if k and m:
                        out.setdefault(k, m.group(1))
            elif tracing.DEVICE_PLANE.match(plane) and OP_NAME_STAT in stats:
                for t in (name, display):
                    k = op_key(t)
                    if k:
                        out[k] = str(stats[OP_NAME_STAT])
    return out


def profile_bounds(path: str) -> Tuple[int, int]:
    """Wall-clock ns of the profile's start and stop."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            return (int(stats["profile_start_time"]),
                    int(stats["profile_stop_time"]))
    raise RuntimeError(f"{path}: no profile_start_time")


def set_scopes(evs: List[dict], names: Dict[Tuple[str, str], str],
               nodes: Iterable[str]) -> None:
    """Gives each device event the ``scope``, the node, that the
    ``op_name`` of its instruction (``op_names``) names."""
    nodes = set(nodes)
    for e in tracing.device_events(evs):
        node = scope_of(names.get(op_key(str(e["name"])), ""), nodes)
        if node is not None:
            e["scope"] = node


def by_node(evs: List[dict], node_names: Iterable[str],
            kernel_patterns: Iterable[str]) -> Dict[str, Dict[str, float]]:
    """Kernel and glue seconds of each node in ``node_names`` (union of
    its events' intervals, averaged over devices), from the events'
    ``scope``; nodes without events are left out."""
    dev = tracing.device_events(evs)
    n = max(1, len({str(e["plane"]) for e in dev}))
    pats = list(kernel_patterns)
    groups: Dict[Tuple[str, str], list] = {}
    for e in dev:
        groups.setdefault((e.get("scope"), str(e["plane"])), []).append(e)
    out: Dict[str, Dict[str, float]] = {}
    for node in node_names:
        busy = kern = 0.0
        planes = [v for (scope, _), v in groups.items() if scope == node]
        if not planes:
            continue
        for mine in planes:
            busy += tracing._length(tracing.union(tracing._intervals(mine)))
            kern += tracing._length(tracing.union(tracing._intervals(
                e for e in mine if tracing.is_kernel(str(e["name"]), pats))))
        out[node] = {"kernel_s": kern / n * 1e-9,
                     "glue_s": (busy - kern) / n * 1e-9}
    return out


def in_stretch(spans: Iterable, t0_ns: int, t1_ns: int) -> list:
    """The spans (``exec.spans.Span``) that overlap [t0_ns, t1_ns]."""
    return [s for s in spans if s.t1_ns >= t0_ns and s.t0_ns <= t1_ns]


def batch_split(spans: Sequence) -> List[dict]:
    """Per ``batcher.batch`` span: its size, its duration, the time of
    each of ``BATCH_PARTS`` and of its ``batcher.coalesce`` (ms), and
    ``unnamed_ms``, the part of the batch no child covers."""
    kids: Dict[int, list] = {}
    coalesce = {s.ids.get("batch"): s for s in spans
                if s.name == "batcher.coalesce"}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    out = []
    for b in spans:
        if b.name != "batcher.batch":
            continue
        row = {"batch": b.ids["batch"], "size": b.ids["count"],
               "batch_ms": (b.t1_ns - b.t0_ns) * 1e-6}
        parts = {p: 0 for p in BATCH_PARTS}
        for k in kids.get(b.id, []):
            parts[k.name] = parts.get(k.name, 0) + k.t1_ns - k.t0_ns
            for g in kids.get(k.id, []):
                parts[g.name] = parts.get(g.name, 0) + g.t1_ns - g.t0_ns
        covered = sum(parts[p] for p in BATCH_PARTS)
        row.update({p + "_ms": v * 1e-6 for p, v in parts.items()})
        c = coalesce.get(b.ids["batch"])
        row["batcher.coalesce_ms"] = ((c.t1_ns - c.t0_ns) * 1e-6
                                      if c is not None else 0.0)
        row["unnamed_ms"] = row["batch_ms"] - covered * 1e-6
        out.append(row)
    return out


def queue_wait_ms(spans: Sequence, batches: Iterable[int]
                  ) -> Optional[float]:
    """Median ``batcher.queue_wait`` (ms) of the requests of ``batches``."""
    batches = set(batches)
    waits = [(s.t1_ns - s.t0_ns) * 1e-6 for s in spans
             if s.name == "batcher.queue_wait" and s.ids["batch"] in batches]
    return statistics.median(waits) if waits else None


def idle_by_span(evs: List[dict], spans: Sequence, t0_ns: int
                 ) -> Dict[str, float]:
    """Idle seconds of the first device, each gap given to the innermost
    span of the micro-batcher's worker open at its midpoint
    (``worker:none`` where none was); ``spans`` on the wall clock, the
    events on the trace's, which starts at ``t0_ns``."""
    dev = tracing.device_events(evs)
    if not dev:
        return {}
    first = sorted({str(e["plane"]) for e in dev})[0]
    merged = tracing.union(tracing._intervals(
        e for e in dev if e["plane"] == first))
    by_id = {s.id: s for s in spans}

    def depth(s):
        d = 0
        while s.parent in by_id:
            s, d = by_id[s.parent], d + 1
        return d

    worker = sorted((s.t0_ns - t0_ns, s.t1_ns - t0_ns, depth(s), s.name)
                    for s in spans if s.thread == "micro-batcher"
                    and s.name != "batcher.queue_wait")
    starts = [w[0] for w in worker]
    out: Dict[str, float] = {}
    for (_, a), (b, _) in zip(merged, merged[1:]):
        mid = (a + b) / 2
        # Spans nest and a batch holds about a dozen: the ones open at
        # ``mid`` start among the last few before it.
        k = bisect.bisect_right(starts, mid)
        inner = max(((d, n) for s0, s1, d, n in worker[max(0, k - 64):k]
                     if s1 >= mid), default=(0, "worker:none"))
        out[inner[1]] = out.get(inner[1], 0.0) + (b - a) * 1e-9
    return out


def summary(evs: List[dict], spans: Sequence, bounds: Tuple[int, int],
            nodes: Sequence[str], patterns: Sequence[str],
            top: int = 10) -> dict:
    """What the ``program_trace`` line reports (see the module doc)."""
    t0, t1 = bounds
    traced = {s.ids["batch"] for s in spans if s.name == "batcher.batch"
              and s.t0_ns >= t0 and s.t1_ns <= t1}
    split = [r for r in batch_split(spans) if r["batch"] in traced]
    keys = ["batch_ms", "batcher.coalesce_ms"] + [
        p + "_ms" for p in BATCH_PARTS] + ["unnamed_ms"]
    mean = {k: (statistics.fmean(r[k] for r in split) if split else None)
            for k in keys}
    red = tracing.reduce(evs, patterns)
    per = by_node(evs, nodes, patterns)
    ranked = sorted(per.items(),
                    key=lambda kv: -(kv[1]["kernel_s"] + kv[1]["glue_s"]))
    scoped = sum(v["kernel_s"] + v["glue_s"] for v in per.values())
    return {
        "batches": len(split),
        "host_ms_per_batch": (statistics.fmean(
            r["batch_ms"] - r["engine.device_wait_ms"] for r in split)
            if split else None),
        "queue_wait_ms_p50": queue_wait_ms(
            spans, [r["batch"] for r in split]),
        "split_mean_ms": mean,
        "batches_covered_0.5ms": (sum(abs(r["unnamed_ms"]) <= 0.5
                                      for r in split) / len(split)
                                  if split else None),
        "idle_s_by_worker_span": dict(sorted(
            idle_by_span(evs, spans, t0).items(), key=lambda kv: -kv[1])),
        "top_nodes": [[k, v["kernel_s"], v["glue_s"]]
                      for k, v in ranked[:top]],
        "nodes_kernel_s": sum(v["kernel_s"] for v in per.values()),
        "kernel_s": red["kernel_s"],
        "scoped_share_of_busy": scoped / red["busy_s"] if red["busy_s"]
        else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "bench"))
    import run as run_mod
    from bench import harness, network
    from repro.exec import spans as prog

    cells = {w["name"]: w for w in
             network.load_json(harness.BENCHMARK_FILE)["workloads"]}
    config = network.load_config(cells[args.workload]["config"])
    nodes = [n["name"] for n in config["nodes"] if n["op"] != "input"]
    patterns = network.load_json(harness.KERNELS_FILE)["taom"]
    kept: dict = {}
    read_events = tracing.events

    def events(path, bench_spans=()):
        t0, t1 = profile_bounds(path)
        mine = in_stretch(prog.drain(), t0, t1)
        evs = read_events(path, list(bench_spans) + [
            (s.name, s.t0_ns, s.t1_ns) for s in mine])
        names = op_names(path)
        set_scopes(evs, names, nodes)
        kept.update(evs=evs, spans=mine, bounds=(t0, t1), names=names)
        return evs

    run_cell = harness.run_cell

    def recorded(*a, **kw):
        prog.record(True)
        try:
            return run_cell(*a, **kw)
        finally:
            prog.record(False)

    harness.run_cell = recorded
    tracing.events = events
    try:
        rc = run_mod.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", str(args.trace)])
    finally:
        harness.run_cell, tracing.events = run_cell, read_events
    if not kept:
        return rc
    line = summary(kept["evs"], kept["spans"], kept["bounds"], nodes,
                   patterns)
    print("program_trace " + json.dumps(line), flush=True)
    if args.out:
        # Device operations as [op, start, duration], each op once with
        # its op_name: enough to read the trace again by hand.
        ops: Dict[str, int] = {}
        table, dev = [], []
        for e in tracing.device_events(kept["evs"]):
            name = str(e["name"])
            if name not in ops:
                ops[name] = len(table)
                table.append({"name": name[:400], "op_name": kept[
                    "names"].get(op_key(name)), "scope": e.get("scope")})
            dev.append([ops[name], e["start_ns"], e["dur_ns"]])
        with gzip.open(args.out, "wt") as f:
            json.dump({"summary": line, "bounds": kept["bounds"],
                       "split": batch_split(kept["spans"]),
                       "spans": [s._asdict() for s in kept["spans"]],
                       "host": [e for e in kept["evs"]
                                if e["plane"] == tracing.HOST_PLANE],
                       "ops": table, "device": dev}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
