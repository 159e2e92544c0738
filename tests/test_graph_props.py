"""Property-based tests for the lowering IR (ISSUE 3 satellite).

Random op-graphs — random strides, odd/rectangular spatial dims,
depthwise/residual/pool mixes — must satisfy:

  * ``graph_apply`` (the im2col/block-diagonal GEMM lowering, exact
    matmul) equals the direct jax.lax.conv reference;
  * ``graph_gemms``'s analytic rows equal the shapes the walker
    actually produces (shape inference is truthful);
  * every planned tile covers its GEMM and its padded dims divide by
    the tile exactly (the kernel's grid arithmetic cannot under-run).

Optional-dependency guard: the whole module skips cleanly when
hypothesis isn't installed (CI images without it still collect).
"""
import pytest

hypothesis = pytest.importorskip("hypothesis")
import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402
from hypothesis import given, settings, strategies as st    # noqa: E402

from repro.core import perf_model as pm                     # noqa: E402
from repro.core.types import Dataflow                       # noqa: E402
from repro.exec import PlanCache                            # noqa: E402
from repro.exec.scheduler import choose_tile, plan_layer    # noqa: E402
from repro.models import lowering as lw                     # noqa: E402
from repro.models.lowering import LayerGemm                 # noqa: E402

HEANA = pm.AcceleratorConfig.equal_area("heana", Dataflow.OS, 1.0)
AMW = pm.AcceleratorConfig.equal_area("amw", Dataflow.OS, 1.0)


@st.composite
def chain_graphs(draw):
    """A random straight-line net with optional residual/pool detours:
    stem conv -> K blocks (conv | depthwise | conv+residual | pool) ->
    global pool -> fc.  Strides and kernel sizes vary; spatial dims are
    drawn odd/rectangular on purpose."""
    h = draw(st.integers(7, 14))
    w = draw(st.integers(7, 14))
    cin = draw(st.integers(1, 3))
    nodes = [lw.input_node(cin),
             lw.conv("stem", "input", draw(st.integers(2, 6)),
                     kk=draw(st.sampled_from([1, 3])),
                     stride=draw(st.sampled_from([1, 2])))]
    prev, prev_c = "stem", nodes[-1].cout
    n_blocks = draw(st.integers(1, 3))
    for i in range(n_blocks):
        kind = draw(st.sampled_from(
            ["conv", "depthwise", "residual", "pool"]))
        name = f"b{i}"
        if kind == "conv":
            cout = draw(st.integers(2, 8))
            nodes.append(lw.conv(name, prev, cout,
                                 kk=draw(st.sampled_from([1, 3, 5])),
                                 stride=draw(st.sampled_from([1, 2])),
                                 relu=draw(st.booleans())))
            prev, prev_c = name, cout
        elif kind == "depthwise":
            nodes.append(lw.dwconv(name, prev,
                                   stride=draw(st.sampled_from([1, 2])),
                                   relu=draw(st.booleans())))
            prev = name
        elif kind == "residual":
            # two parallel 1x1 convs to the same channel count, added
            cout = draw(st.integers(2, 6))
            nodes.append(lw.conv(f"{name}_l", prev, cout, kk=1))
            nodes.append(lw.conv(f"{name}_r", prev, cout, kk=3))
            nodes.append(lw.residual(name, f"{name}_l", f"{name}_r",
                                     relu=draw(st.booleans())))
            prev, prev_c = name, cout
        else:
            # 'same'-padded max pool tiles any dims (odd included)
            nodes.append(lw.pool(name, prev, kind="max",
                                 size=draw(st.sampled_from([2, 3])),
                                 stride=draw(st.sampled_from([1, 2])),
                                 padding="same"))
            prev = name
    nodes.append(lw.global_avg("gap", prev))
    nodes.append(lw.fc("out", "gap", draw(st.integers(2, 5))))
    return lw.OpGraph(tuple(nodes)), (h, w)


@settings(max_examples=25, deadline=None)
@given(chain_graphs(), st.integers(0, 2 ** 31 - 1))
def test_lowered_apply_equals_direct_reference(graph_hw, seed):
    """The GEMM lowering computes the same function as lax.conv —
    strides, odd/rect dims, depthwise, residual and pooling included."""
    graph, in_hw = graph_hw
    key = jax.random.PRNGKey(seed)
    params = lw.init_params(graph, key, in_hw)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (2, *in_hw, graph.input.cout))
    got = lw.graph_apply(params, x, graph)
    want = lw.direct_forward(params, x, graph)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=25, deadline=None)
@given(chain_graphs())
def test_graph_gemms_are_truthful(graph_hw):
    """Analytic rows == executed rows: every conv/fc LayerGemm's C is
    exactly the pixel count the walker produces at that node."""
    graph, in_hw = graph_hw
    shapes = lw.infer_shapes(graph, in_hw)
    gemms = lw.graph_gemms(graph, in_hw)
    assert [g.name for g in gemms] == [n.name for n in graph.gemm_nodes]
    for g, node in zip(gemms, graph.gemm_nodes):
        oh, ow, oc = shapes[node.name]
        if node.op == "fc":
            assert g.c == 1 and g.d == oc
        elif node.op == "depthwise_conv":
            assert g.c == oh * ow and g.d == 1
            assert g.count == shapes[node.inputs[0]][2]
        else:
            assert g.c == oh * ow and g.d == oc
        assert g.macs > 0


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 5000), st.integers(1, 600), st.integers(1, 3000))
def test_planned_tile_divides_its_gemm_dims(m, d, k):
    """Every tile covers the GEMM and the padded dims divide exactly by
    the chosen blocks — the kernel's grid can neither under-run nor
    leave a ragged last step."""
    t = choose_tile(m, d, k, dpe_size=83)
    mp = t.grid_m * t.block_m
    dp = t.grid_d * t.block_d
    assert mp >= m and dp >= d
    assert mp % t.block_m == 0 and dp % t.block_d == 0
    assert mp - t.block_m < m       # no superfluous trailing grid step
    assert dp - t.block_d < d
    assert t.block_m % 8 == 0 and t.block_d % 128 == 0
    assert t.n_chunks >= 1


@pytest.mark.parametrize("acc", [HEANA, AMW], ids=["heana", "amw"])
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4000), st.integers(1, 500), st.integers(1, 2000),
       st.integers(2, 64))
def test_depthwise_plan_tile_covers_executed_gemm(acc, m, d, k, count):
    """Depthwise layers plan their tile against what the executor
    actually runs: the grouped taps (M pixels x count channels) under
    analog carry, the fused block-diagonal GEMM (M, k*count) @
    (k*count, count) under chunk ADC.  Either way the noise scale's
    fold count is that of K = k*count."""
    layer = LayerGemm("dw", m, k, 1, count=count)
    plan = plan_layer(layer, acc, cache=PlanCache())
    assert plan.tile.grid_d * plan.tile.block_d >= count
    assert plan.tile.grid_m * plan.tile.block_m >= m
    assert plan.tile.n_chunks == max(1, -(-k * count // acc.n))
    grouped = acc.backend == "heana"
    assert layer.executed(acc.backend) == (
        (m, k, count) if grouped else (m, k * count, count))
