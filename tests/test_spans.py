"""Host spans and counters of the serving path (exec.spans, exec.serving)
and the node scopes of the compiled forward.

Recording is off by default and switched for the whole process, so each
test that turns it on turns it off again and drains what it recorded.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import hw
from repro.core.types import Dataflow
from repro.exec import (MicroBatcher, PlanCache, ServingEngine, executor,
                        spans)
from repro.models import zoo_cnn
from repro.models.cnn import build_small_cnn

OP = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                  noise_enabled=False)
ENGINE_CHILDREN = ["engine.pad", "engine.validate", "engine.dispatch",
                   "engine.slice", "engine.device_wait"]


@pytest.fixture(scope="module")
def engine():
    eng = ServingEngine(build_small_cnn(jax.random.PRNGKey(0)), OP,
                        max_batch=4, plan_cache=PlanCache())
    eng.warmup()
    return eng


@pytest.fixture
def recording():
    spans.drain()
    spans.record(True)
    try:
        yield
    finally:
        spans.record(False)
        spans.drain()


def _images(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))


def _serve(engine, n, max_delay_s=0.05):
    """``n`` requests queued before the worker starts, so the batches are
    fixed: full ones of 4, then the rest."""
    mb = MicroBatcher(engine, max_delay_s=max_delay_s)
    imgs = _images(n, seed=n)
    futs = [mb.submit(imgs[i]) for i in range(n)]
    mb.start()
    for f in futs:
        assert f.result(timeout=120).shape == (10,)
    mb.stop()
    return mb


def _dur(s):
    return s.t1_ns - s.t0_ns


def test_span_nests_on_its_thread_and_add_has_no_parent(recording):
    with spans.span("outer", request=7) as outer:
        with spans.span("inner", batch=3):
            pass
        spans.add("elsewhere", 1, 2, request=7)
    got = {s.name: s for s in spans.drain()}
    assert got["inner"].parent == got["outer"].id
    assert got["outer"].parent is None and got["elsewhere"].parent is None
    assert got["outer"].ids == {"request": 7}
    assert got["inner"].ids == {"batch": 3}
    assert (got["outer"].t0_ns, got["outer"].t1_ns) == (outer.t0, outer.t1)
    assert got["outer"].t0_ns <= got["inner"].t0_ns <= got["inner"].t1_ns \
        <= got["outer"].t1_ns
    assert {s.thread for s in got.values()} == {"MainThread"}
    assert spans.drain() == []


def test_recording_off_stores_nothing_and_counters_count(engine):
    spans.drain()
    with spans.span("timed") as s:
        pass
    assert s.t1 >= s.t0
    before = engine.stats()
    mb = _serve(engine, 3)
    assert spans.drain() == []
    st = mb.stats()
    assert st["batches_formed"] == 1 and st["requests_batched"] == 3
    assert st["queue_wait_s_total"] >= st["queue_wait_s_max"] > 0
    assert st["batch_host_s_total"] > 0
    assert st["batch_device_wait_s_total"] > 0
    assert st["result_reads"] == st["batches_formed"]
    assert st["result_read_s_total"] > 0
    after = engine.stats()
    assert after["infer_s_total"] > before["infer_s_total"]
    assert after["device_wait_s_total"] > before["device_wait_s_total"]


def test_micro_batcher_spans_nest_and_match_counters(engine, recording):
    n = 6
    before = engine.stats()
    mb = _serve(engine, n)
    st, after = mb.stats(), engine.stats()
    rec = spans.drain()
    by_id = {s.id: s for s in rec}

    def named(name):
        return [s for s in rec if s.name == name]

    def children(s):
        return sorted((c for c in rec if c.parent == s.id),
                      key=lambda c: c.t0_ns)

    waits, batches = named("batcher.queue_wait"), named("batcher.batch")
    assert sorted(w.ids["request"] for w in waits) == list(range(n))
    assert len(batches) == st["batches_formed"] == 2
    assert [(b.ids["request"], b.ids["count"]) for b in
            sorted(batches, key=lambda b: b.t0_ns)] == [(0, 4), (4, 2)]
    assert sorted(c.ids["batch"] for c in named("batcher.coalesce")) == \
        sorted(b.ids["batch"] for b in batches)

    batch_of = {b.ids["batch"]: b for b in batches}
    for w in waits:
        b = batch_of[w.ids["batch"]]
        assert b.ids["request"] <= w.ids["request"] \
            < b.ids["request"] + b.ids["count"]
        # Submit on the caller's thread, dispatch on the worker's: one
        # clock, so the wait ends where its batch starts.
        assert w.t0_ns <= w.t1_ns == b.t0_ns

    host = wait = 0
    for b in batches:
        kids = children(b)
        assert [k.name for k in kids] == ["batcher.stack", "engine.infer",
                                          "batcher.scatter"]
        infer, scatter = kids[1], kids[2]
        assert [k.name for k in children(infer)] == ENGINE_CHILDREN
        assert [k.name for k in children(scatter)] == ["batcher.read"]
        for k in kids + children(infer) + children(scatter):
            assert k.thread == "micro-batcher"
            parent = by_id[k.parent]
            assert parent.t0_ns <= k.t0_ns <= k.t1_ns <= parent.t1_ns
        dw = children(infer)[-1]
        host += _dur(b) - _dur(dw)
        wait += _dur(dw)

    assert st["queue_wait_s_total"] == pytest.approx(
        sum(_dur(w) for w in waits) * 1e-9, rel=1e-12)
    assert st["queue_wait_s_max"] == pytest.approx(
        max(_dur(w) for w in waits) * 1e-9, rel=1e-12)
    assert st["batch_host_s_total"] == pytest.approx(host * 1e-9, rel=1e-12)
    assert st["batch_device_wait_s_total"] == pytest.approx(wait * 1e-9,
                                                            rel=1e-12)
    reads = named("batcher.read")
    assert len(reads) == st["result_reads"] == st["batches_formed"]
    assert st["result_read_s_total"] == pytest.approx(
        sum(_dur(r) for r in reads) * 1e-9, rel=1e-12)
    infers = named("engine.infer")
    assert after["infer_s_total"] - before["infer_s_total"] == \
        pytest.approx(sum(_dur(s) for s in infers) * 1e-9, rel=1e-9)
    assert after["device_wait_s_total"] - before["device_wait_s_total"] == \
        pytest.approx(sum(_dur(s) for s in named("engine.device_wait"))
                      * 1e-9, rel=1e-9)


def test_direct_infer_records_engine_spans(engine, recording):
    engine.infer(_images(3))
    rec = spans.drain()
    (infer,) = [s for s in rec if s.name == "engine.infer"]
    assert infer.parent is None and infer.thread == "MainThread"
    kids = sorted((s for s in rec if s.parent == infer.id),
                  key=lambda s: s.t0_ns)
    assert [k.name for k in kids] == ENGINE_CHILDREN


def test_node_scopes_name_every_node_in_the_lowered_forward():
    model = zoo_cnn.PAPER_ZOO["resnet_mini"]
    params = model.init_params(jax.random.PRNGKey(0))
    plan = executor.plan_for_network(params, OP, batch=2, in_hw=model.in_hw,
                                     lowering=model.graph)
    x = jax.ShapeDtypeStruct((2,) + tuple(model.in_hw) + (model.in_ch,),
                             jnp.float32)
    text = executor.forward_fn.lower(
        params, x, None, lowering=model.graph, plan=plan,
        cfg=OP.kernel_config(), impl="pallas", collect_activations=False,
        mesh=None).as_text(debug_info=True)
    nodes = [n.name for n in model.graph.nodes if n.op != "input"]
    assert len(nodes) > 10
    assert [n for n in nodes if f'_forward)/{n}/' not in text] == []
