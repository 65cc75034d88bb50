"""python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, warms up exactly its own programs (set-up), measures
for --seconds, checks the outputs against the plain reference, prints
the contract's one JSON object as the last line of stdout, exits. It
needs a TPU with the chips the cell asks for and refuses to run
anywhere else. See perf/README.md."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory off the path (perf/trace.py must not shadow
# the standard library's), the checkout's root on it
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perf import harness

    bench = harness.load_json("BENCHMARK.json")
    cell = harness.load_json("perf", "workloads", f"{args.workload}.json")
    config = harness.load_json("perf", "configs", f"{cell['config']}.json")
    devices = harness.require_tpu(cell["chips"])

    from paddle_tpu import jax_cache

    from perf import models

    cache_dir = jax_cache.configure()
    run = harness.Run(bench, cell, config, args.seed, args.seconds,
                      bool(args.trace), T_START)
    run.devices = devices
    harness.say(f"perf: cell {cell['name']} seed {args.seed} seconds "
                f"{args.seconds} trace {args.trace}; platform "
                f"{devices[0].platform}, device_kind "
                f"{devices[0].device_kind}, {len(devices)} device(s), "
                f"{cell['chips']} used; jax cache {cache_dir}")
    models.kind(cell["kind"]).run(run)
    harness.say(f"perf: memory_stats "
                f"{harness.memory_stats_line(devices[:cell['chips']])}")
    line = harness.result_line(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
