"""A run end to end on the CPU at a small size, with the chip check
skipped: sound, it is correct; with the timed path broken underneath,
or with the control in the program's place, it is not."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, network, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = network.load_json(harness.BENCHMARK_FILE)
#: The small configurations' own limit: their served rows are bitwise
#: the reference's (gap 0), the bfloat16 control moves every row by about
#: 5-20%, and an altered answer by about 100% or more.
LIMITS = {"checks": {"logit_rel_err.max": {"limit": 1e-3}}}
CLOSED = {"kind": "closed", "request_images": 8, "max_batch": 8, "ring": 2,
          "check_requests": 2}
POISSON = {"kind": "poisson", "rate_per_s": 150, "request_images": 1,
           "max_batch": 4, "max_delay_s": 0.002, "image_pool": 16,
           "check_requests": 6, "drain_s": 0.5}


def _run(config, mix, cell="resnet50-heana.bulk-b64", trace=False,
         control=None, limits=LIMITS):
    config = network.load_json(os.path.join(DATA, f"{config}.json"))
    return harness.run_cell(
        {"name": cell, "chips": 1}, config, mix, 2 ** 33 + 12345, 1.0,
        trace, bench=BENCH, limits=limits, t_process=time.perf_counter(),
        require_tpu=False, control=control)


def _cell_limits(cell):
    return network.load_json(os.path.join(harness.LIMITS_DIR,
                                          f"{cell}.json"))


@pytest.mark.parametrize("config,mix,cell", [
    ("tiny-heana", CLOSED, "resnet50-heana.bulk-b64"),
    ("tiny-amw", CLOSED, "resnet50-heana.bulk-b64"),
    ("tiny-heana", POISSON, "resnet50-heana.poisson-b1"),
])
def test_sound_run_is_correct(config, mix, cell):
    res = _run(config, mix, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["_detail"]["in_window"] == {"compiles": 0, "traces": 0}
    assert list(res)[-2:] == ["checks", "_detail"]
    names = {m["name"] for m in harness.cell_metrics(BENCH, cell, False)}
    assert set(res["metrics"]) == names


@pytest.mark.parametrize("config,mix,cell", [
    ("tiny-heana", CLOSED, "resnet50-heana.bulk-b64"),
    ("tiny-amw", CLOSED, "resnet50-heana.bulk-b64"),
    ("tiny-heana", POISSON, "resnet50-heana.poisson-b1"),
])
def test_control_is_not_correct_under_the_cell_limits(config, mix, cell):
    """The bfloat16 reference in the program's place, judged by the
    cell's committed limits, comes out not correct; the program's own
    readings on the same batches pass them."""
    limits = _cell_limits(cell)
    res = _run(config, mix, cell, control="bfloat16", limits=limits)
    assert not res["correct"], res["checks"]
    prog = res["_detail"]["program_checks"]
    assert all(c["value"] <= c["limit"] for c in prog.values()), prog


@pytest.fixture
def altered_answer(monkeypatch):
    """The program's engine returns one row of each batch negated: an
    answer altered where it is produced."""
    from repro.exec.serving import ServingEngine
    orig = ServingEngine._run_bucket

    def broken(self, xb, key, bucket):
        return orig(self, xb, key, bucket).at[0].multiply(-1.0)
    monkeypatch.setattr(ServingEngine, "_run_bucket", broken)


@pytest.mark.parametrize("mix", [CLOSED, dict(POISSON, rate_per_s=40)])
def test_altered_answer_is_not_correct(altered_answer, mix):
    res = _run("tiny-heana", mix)
    assert not res["correct"], res["checks"]


def test_a_trace_without_device_events_gives_no_result():
    """On the CPU the trace has no TPU plane: the run stops rather than
    report a line without its per-layer metrics."""
    with pytest.raises(SystemExit, match="device"):
        _run("tiny-heana", CLOSED, trace=True)


def test_answers_after_the_close_count_as_failed():
    reqs = [traffic.Request(0, 1, 0.1, 0.1, 0.2),
            traffic.Request(1, 1, 0.9, 0.9, 1.7),
            traffic.Request(2, 1, 1.0, 1.0, None)]
    win = traffic.Window(reqs, {0: 0, 1: 0}, 0.0, 1.5, [], [], {}, [])
    assert traffic.failed(win) == 2
    assert traffic.latencies_ms(win) == pytest.approx(
        [100.0, 800.0, 1e3 * (1.5 + traffic.ANSWER_WAIT_S - 1.0)])


def test_every_seed_gets_the_same_arrivals():
    mix = {"rate_per_s": 200}
    a = traffic.arrival_gaps(mix, 10.0, 1)
    b = traffic.arrival_gaps(mix, 10.0, 2 ** 40 + 3)
    assert len(a) == 2000 and not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert a.sum() == pytest.approx(10.0)


def _cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "resnet50-heana.poisson-b1", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_result_without_a_tpu():
    out = _cmd(network.REPO_DIR)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_no_result_without_the_program(tmp_path):
    shutil.copy(harness.BENCHMARK_FILE, tmp_path / "BENCHMARK.json")
    shutil.copytree(network.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cmd(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "program is not in this checkout" in out.stderr


def test_result_line_is_json_with_the_contract_keys(capsys, monkeypatch):
    """run.py's output on the CPU with the chip check stubbed out."""
    sys.path.insert(0, os.path.join(network.BENCH_DIR))
    import run as run_mod
    monkeypatch.setattr(harness, "device_info",
                        lambda chips, require: {"platform": "cpu",
                                                "kind": "cpu", "count": 1})
    monkeypatch.setattr(network, "load_config", lambda name: network.load_json(
        os.path.join(DATA, "tiny-heana.json")))
    monkeypatch.setattr(network, "load_traffic", lambda name: CLOSED)
    from bench import program
    monkeypatch.setattr(program, "use_persistent_cache", lambda: None)
    assert run_mod.main(["--workload", "resnet50-heana.poisson-b1", "--seed",
                         "7", "--seconds", "1", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert err.strip().splitlines()[-1].startswith("check logit_rel_err")
