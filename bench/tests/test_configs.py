"""Each configuration runs the published network: its GEMM table equals
the paper's analytic table (``models.cnn.CNN_ZOO``) layer by layer, and
the program's lowering of it agrees."""
import json
import os

import pytest

from bench import inputs, network, program
from repro.models import cnn
from repro.models import lowering as lw

BENCH = json.load(open(os.path.join(network.REPO_DIR, "BENCHMARK.json")))
ZOO_NAME = {"resnet50-heana": "resnet50", "mobilenet_v2-amw": "mobilenet_v2"}


@pytest.mark.parametrize("name", sorted(ZOO_NAME))
def test_gemms_equal_the_paper_table(name):
    config = network.load_config(name)
    got = [(g.name, g.m, g.k, g.d, g.count)
           for g in network.logical_gemms(config)]
    want = [(g.name, g.c, g.k, g.d, g.count)
            for g in cnn.CNN_ZOO[ZOO_NAME[name]]()]
    assert got == want


@pytest.mark.parametrize("name", sorted(ZOO_NAME))
def test_program_lowering_agrees(name):
    config = network.load_config(name)
    prog = lw.graph_gemms(program.graph(config), tuple(config["input_hw"]))
    mine = network.logical_gemms(config)
    assert [(g.name, g.c, g.k, g.d, g.count) for g in prog] == \
        [(g.name, g.m, g.k, g.d, g.count) for g in mine]


@pytest.mark.parametrize("name", sorted(ZOO_NAME))
def test_operating_point_is_the_stated_one(name):
    config = network.load_config(name)
    op = program.operating_point(config)
    assert op.n == config["operating_point"]["dpe_size"]
    bad = json.loads(json.dumps(config))
    bad["operating_point"]["noise_sigma_int"] += 1.0
    with pytest.raises(ValueError, match="not the one"):
        program.operating_point(bad)


def test_weight_shapes_cover_every_gemm():
    config = network.load_config("mobilenet_v2-amw")
    shapes = dict(inputs.weight_shapes(config))
    assert shapes["conv1"] == (27, 32)
    assert shapes["mb24_1_dw"] == (9, 144)
    assert shapes["fc"] == (1280, 1000)
    assert len(shapes) == 53


def test_benchmark_entries_point_at_files():
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(network.REPO_DIR, c["file"]))
        assert network.load_config(c["name"])["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        network.load_config(w["config"])
        network.load_traffic(w["traffic"])
        assert os.path.exists(os.path.join(network.BENCH_DIR, "limits",
                                           w["name"] + ".json"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.exists(os.path.join(network.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
