"""``mobilenet_v2-heana`` runs the published MobileNetV2 on the stated
HEANA point: its GEMM table is the paper's (``CNN_ZOO["mobilenet_v2"]``),
its nodes are ``mobilenet_v2-amw``'s, and its operating point is
``resnet50-heana``'s."""
from bench import network, program
from repro.models import cnn
from repro.models import lowering as lw

CONFIG = network.load_config("mobilenet_v2-heana")


def test_gemms_equal_the_paper_table():
    got = [(g.name, g.m, g.k, g.d, g.count)
           for g in network.logical_gemms(CONFIG)]
    want = [(g.name, g.c, g.k, g.d, g.count)
            for g in cnn.CNN_ZOO["mobilenet_v2"]()]
    assert got == want
    assert sum(g.count > 1 for g in network.logical_gemms(CONFIG)) == 17


def test_nodes_are_the_amw_configurations():
    assert CONFIG["nodes"] == network.load_config("mobilenet_v2-amw")["nodes"]
    assert CONFIG["input_hw"] == [224, 224] and CONFIG["reduced"] == []


def test_operating_point_is_the_stated_one():
    stated = network.load_config("resnet50-heana")["operating_point"]
    assert CONFIG["operating_point"] == stated
    op = program.operating_point(CONFIG)
    assert (op.backend, op.n, op.bits, op.adc_bits) == ("heana", 83, 4, 8)
    assert not op.noise_enabled


def test_depthwise_layers_run_grouped():
    """The program plans every depthwise layer as grouped taps."""
    gemms = lw.graph_gemms(program.graph(CONFIG), (224, 224))
    dw = [g for g in gemms if g.depthwise]
    assert len(dw) == 17
    assert all(g.executed("heana") == (g.c, 9, g.count) for g in dw)
