"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Set-up
(device, weights, images, planning, compile or cache load) is timed from
process start to the first timed request, the window runs for
``--seconds``, and the logits the window returned are then checked
against the plain reference.  ``--trace 1`` records a profiler trace of
a few seconds of the window and reports the cell's per-layer metrics in
place of its end-to-end ones.

Standard output ends with one JSON line (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` with ``--trace 1``,
and ``checks``, each compared number beside its limit); standard error
ends with the same checks.  The run exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for, and where
the program is not in the checkout.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness, network
    bench = network.load_json(harness.BENCHMARK_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"bench: no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    try:
        from bench import program
    except ImportError as e:
        sys.exit(f"bench: the program is not in this checkout ({e})")
    import jax
    program.use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    config = network.load_config(cell["config"])
    mix = network.load_traffic(cell["traffic"])
    limits = network.load_json(os.path.join(harness.LIMITS_DIR,
                                            f"{cell['name']}.json"))
    result = harness.run_cell(
        cell, config, mix, args.seed, args.seconds, bool(args.trace),
        bench=bench, limits=limits, t_process=T_PROCESS)
    detail = result.pop("_detail")
    print("setup " + json.dumps(detail["setup"]), flush=True)
    print("in_window " + json.dumps(detail["in_window"]), flush=True)
    print("simulated (photonic model, not the TPU) "
          + json.dumps(detail["simulated"]), flush=True)
    print("generator_late_ms " + json.dumps(detail["generator_late_ms"]),
          flush=True)
    print("counters " + json.dumps(detail["counters"], default=str),
          flush=True)
    if detail["traced"]:
        print("traced " + json.dumps(detail["traced"]), flush=True)
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
