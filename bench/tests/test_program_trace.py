"""The program's spans and node scopes against a trace
(``program_trace.py``), and the readers of the program's serving
counters."""
import time
import types

import pytest

from bench import harness, program_trace as pt, tracing
from repro.exec import spans
from repro.exec.spans import Span

DEV = "/device:TPU:0"
W = "micro-batcher"


def ev(name, start, dur, scope=None, plane=DEV):
    e = {"plane": plane, "line": "XLA Ops", "name": name,
         "start_ns": float(start), "dur_ns": float(dur)}
    if scope is not None:
        e["scope"] = scope
    return e


def test_scope_of_finds_the_node_in_an_op_name():
    nodes = {"conv1", "s1b0_3x3"}
    assert pt.scope_of("jit(_forward)/s1b0_3x3/jit(_pad)/pad", nodes) == \
        "s1b0_3x3"
    assert pt.scope_of("jit(_forward)/concatenate", nodes) is None
    assert pt.scope_of("", nodes) is None


def test_by_node_splits_kernel_and_glue_per_node():
    evs = [ev("pad.1", 0, 100, "conv1"),
           ev("taom_analog_carry.1 tpu_custom_call", 100, 300, "conv1"),
           ev("relu.1", 350, 100, "conv1"),        # overlaps the kernel
           ev("taom_analog_carry.2 tpu_custom_call", 1000, 200, "fc"),
           ev("copy.9", 1200, 50),                 # no scope
           ev("bench.sleep", 0, 2000, plane=tracing.HOST_PLANE)]
    per = pt.by_node(evs, ["conv1", "fc", "pool"], ["tpu_custom_call"])
    assert list(per) == ["conv1", "fc"]
    assert per["conv1"]["kernel_s"] == pytest.approx(300e-9)
    assert per["conv1"]["glue_s"] == pytest.approx(150e-9)
    assert per["fc"] == {"kernel_s": pytest.approx(200e-9),
                         "glue_s": pytest.approx(0.0)}
    red = tracing.reduce(evs, ["tpu_custom_call"])
    assert sum(v["kernel_s"] for v in per.values()) == \
        pytest.approx(red["kernel_s"])


def test_op_key_ignores_layouts():
    trace = ("%pad.104 = f32[25088,581]{1,0:T(8,128)} pad(f32[25088,576]"
             "{1,0:T(8,128)} %bitcast.609, f32[]{:T(128)} %constant.68), "
             "padding=0_0x0_5")
    hlo = ("  ROOT %pad.104 = f32[25088,581]{1,0} pad(%bitcast.609, "
           "%constant.68), padding=0_0x0_5, metadata={op_name=\"x\"}")
    assert pt.op_key(trace) == pt.op_key(hlo) == ("%pad.104",
                                                   "f32[25088,581]")
    tup = ("%fusion.3 = (f32[8,4]{1,0:T(8,128)}, /*index=1*/f32[8]{0}) "
           "fusion(f32[8,4]{1,0} %p), kind=kLoop")
    assert pt.op_key(tup) == ("%fusion.3", "(f32[8,4], f32[8])")
    assert pt.op_key("not an instruction") is None


def test_op_names_read_the_named_scopes_from_a_recorded_trace():
    """A trace recorded here: the profiler keeps the HLO of the programs
    it saw, with each instruction's ``op_name``, beside the trace."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("conv1"):
            y = jnp.sin(x) @ x
        with jax.named_scope("fc"):
            return jnp.tanh(y) + 1.0

    fn = jax.jit(f)
    x = jnp.ones((16, 16))
    fn(x).block_until_ready()
    cap = tracing.Capture()
    cap.start()
    try:
        fn(x).block_until_ready()
        names = pt.op_names(cap.stop())
    finally:
        cap.close()
    scopes = {pt.scope_of(v, {"conv1", "fc"}) for v in names.values()}
    assert {"conv1", "fc"} <= scopes
    dot = [v for (name, _), v in names.items() if name.startswith("%dot")]
    assert dot and all("/conv1/" in v for v in dot)


def test_set_scopes_names_each_device_event_by_its_op_name():
    evs = [ev("%a.1 = f32[2]{0:T(128)} add(f32[2] %x)", 0, 1),
           ev("%b.2 = f32[2]{0} copy(f32[2] %y)", 1, 1),
           ev("bench.sleep", 0, 2, plane=tracing.HOST_PLANE)]
    names = {("%a.1", "f32[2]"): "jit(_forward)/conv1/add"}
    pt.set_scopes(evs, names, ["conv1"])
    assert evs[0]["scope"] == "conv1"
    assert "scope" not in evs[1] and "scope" not in evs[2]


def _batch(bid, first, count, t0, parts, sid):
    """A ``batcher.batch`` span from ``t0`` with its children laid end to
    end: ``parts`` is [(name, ns)], engine.* ones under engine.infer."""
    out = []
    b_id, i_id = sid, sid + 1
    t = t0
    engine = [(n, d) for n, d in parts if n.startswith("engine.")]
    for name, d in parts:
        if name == "engine.infer":
            i0 = t
            for en, ed in engine:
                out.append(Span(sid + 2 + len(out), en, t, t + ed, W, i_id,
                                {}))
                t += ed
            out.append(Span(i_id, name, i0, t, W, b_id, {}))
        elif not name.startswith("engine."):
            out.append(Span(sid + 2 + len(out), name, t, t + d, W, b_id,
                            {}))
            t += d
    out.append(Span(b_id, "batcher.batch", t0, t + 100_000, W, None,
                    {"batch": bid, "request": first, "count": count}))
    return out


PARTS = [("batcher.stack", 3_000_000), ("engine.infer", 0),
         ("engine.pad", 100_000), ("engine.validate", 200_000),
         ("engine.dispatch", 1_000_000), ("engine.slice", 50_000),
         ("engine.device_wait", 12_000_000), ("batcher.scatter", 8_000_000)]


def test_batch_split_and_summary_on_hand_made_spans():
    rec = (_batch(0, 0, 2, 1_000_000, PARTS, 100)
           + _batch(1, 2, 1, 40_000_000, PARTS, 200)
           + [Span(1, "batcher.coalesce", 0, 1_000_000, W, None,
                   {"batch": 0}),
              Span(2, "batcher.queue_wait", 0, 1_000_000, "g", None,
                   {"request": 0, "batch": 0}),
              Span(3, "batcher.queue_wait", 500_000, 1_000_000, "g", None,
                   {"request": 1, "batch": 0}),
              Span(4, "batcher.queue_wait", 30_000_000, 40_000_000, "g",
                   None, {"request": 2, "batch": 1})])
    split = pt.batch_split(rec)
    assert [r["batch"] for r in split] == [0, 1]
    r = split[0]
    assert r["size"] == 2 and r["batcher.coalesce_ms"] == pytest.approx(1.0)
    assert r["engine.device_wait_ms"] == pytest.approx(12.0)
    assert r["batcher.scatter_ms"] == pytest.approx(8.0)
    assert r["unnamed_ms"] == pytest.approx(0.1)
    assert r["batch_ms"] == pytest.approx(24.45)
    assert split[1]["batcher.coalesce_ms"] == 0.0
    # Only batch 0 lies inside the stretch.
    line = pt.summary([ev("k tpu_custom_call", 0, 10, "conv1")], rec,
                      (0, 30_000_000), ["conv1"], ["tpu_custom_call"])
    assert line["batches"] == 1
    assert line["host_ms_per_batch"] == pytest.approx(24.45 - 12.0)
    assert line["queue_wait_ms_p50"] == pytest.approx(0.75)
    assert line["batches_covered_0.5ms"] == 1.0
    assert line["nodes_kernel_s"] == pytest.approx(line["kernel_s"])
    assert line["scoped_share_of_busy"] == pytest.approx(1.0)
    assert line["top_nodes"] == [["conv1", pytest.approx(10e-9),
                                  pytest.approx(0.0)]]


def test_idle_gaps_go_to_the_innermost_worker_span():
    rec = _batch(0, 0, 1, 1_000_000, PARTS, 100)
    scatter = next(s for s in rec if s.name == "batcher.scatter")
    stack = next(s for s in rec if s.name == "batcher.stack")
    t0 = 500_000        # the trace's start on the wall clock
    dev = [ev("a", 0, stack.t0_ns + 1_000_000 - t0),        # into stack
           ev("b", stack.t1_ns - t0, scatter.t0_ns + 1_000_000 - stack.t1_ns),
           ev("c", scatter.t1_ns - t0, 1_000),
           ev("d", scatter.t1_ns - t0 + 5e9, 1_000)]
    idle = pt.idle_by_span(dev, rec, t0)
    assert idle["batcher.stack"] == pytest.approx(2e-3)
    assert idle["batcher.scatter"] == pytest.approx(7e-3)
    assert idle["worker:none"] == pytest.approx(5.0, rel=1e-6)


def test_program_and_bench_spans_share_the_trace_clock():
    """A trace recorded here (no device plane): the benchmark's span and
    the program's, both on the wall clock, land on the trace by its
    start, and an idle gap between hand-placed device operations is
    named by the program span open in it."""
    from bench import traffic
    cap = tracing.Capture()
    tracer = traffic.Tracer(cap, 0.0)
    spans.drain()
    spans.record(True)
    try:
        tracer.tick(0.0)
        with tracer.span("bench.sleep"):
            time.sleep(0.002)
        with spans.span("batcher.batch", batch=0, request=0, count=1):
            with spans.span("batcher.scatter"):
                time.sleep(0.004)
        tracer.finish()
    finally:
        spans.record(False)
    try:
        t0, t1 = pt.profile_bounds(cap.path)
        mine = pt.in_stretch(spans.drain(), t0, t1)
        evs = tracing.events(cap.path, list(tracer.spans) + [
            (s.name, s.t0_ns, s.t1_ns) for s in mine])
    finally:
        cap.close()
    host = {e["name"]: e for e in tracing.host_spans(evs)}
    assert set(host) == {"bench.sleep", "batcher.batch", "batcher.scatter"}
    sleep, scatter = host["bench.sleep"], host["batcher.scatter"]
    assert 0 <= sleep["start_ns"]
    assert sleep["start_ns"] + sleep["dur_ns"] <= scatter["start_ns"]
    assert scatter["start_ns"] + scatter["dur_ns"] <= t1 - t0
    mid = scatter["start_ns"] + scatter["dur_ns"] / 2
    dev = [ev("a", mid - 2e6, 1e6), ev("b", mid + 1e6, 1e5)]
    gaps = tracing.idle_gaps(dev + list(host.values()))
    assert gaps[0][0] == "host:batcher.batch+batcher.scatter"


def _ctx(batcher):
    return types.SimpleNamespace(counters={"engine": {}, "batcher": batcher})


@pytest.mark.parametrize("metric,want", [
    ("queue_wait_ms.serve", 1e3 * 0.6 / 40),
    ("host_ms_per_batch.serve", 1e3 * 0.2 / 8),
])
def test_counter_readers(metric, want):
    read = harness.load_reader(metric)
    stats = {"batches_formed": 8, "requests_batched": 40, "mean_fill": 5.0,
             "queue_wait_s_total": 0.6, "queue_wait_s_max": 0.05,
             "batch_host_s_total": 0.2, "batch_device_wait_s_total": 0.1}
    assert read(_ctx(stats)) == pytest.approx(want)
    # A program without the counters (or with no batch) reads nothing.
    assert read(_ctx({"batches_formed": 8, "requests_batched": 40,
                      "mean_fill": 5.0})) is None
    assert read(_ctx(dict(stats, batches_formed=0,
                          requests_batched=0))) is None
    assert read(types.SimpleNamespace(counters={"engine": {}})) is None

