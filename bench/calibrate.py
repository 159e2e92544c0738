"""Readings that the correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--out FILE]

For each seed, in one process: a run of the cell with a short window
and the control (the reference computed in bfloat16) put in the
program's place, so that the run's ``correct`` and ``checks`` are the
control's, judged against the cell's limits, and the program's own
readings (the served logits against the float32 reference, on the same
batches) are beside them.  The program's readings over a dozen seeds
give each limit's lower reading, the control's its upper one.  Prints
one JSON line per seed.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)

    from bench import harness, network, program
    import jax
    program.use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = network.load_json(harness.BENCHMARK_FILE)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = network.load_config(cell["config"])
    mix = network.load_traffic(cell["traffic"])
    limits = network.load_json(os.path.join(harness.LIMITS_DIR,
                                            f"{cell['name']}.json"))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = harness.run_cell(cell, config, mix, seed, args.seconds, False,
                               bench=bench, limits=limits,
                               t_process=t, control="bfloat16")
        d = res["_detail"]
        line = {"cell": cell["name"], "seed": seed,
                "attempted": res["attempted"], "failed": res["failed"],
                "program": d["program_checks"],
                "control_correct": res["correct"],
                "control": res["checks"], "row_gaps": d["row_gaps"],
                "metrics": res["metrics"], "in_window": d["in_window"],
                "setup": d["setup"], "run_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
