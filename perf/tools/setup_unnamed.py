"""python perf/tools/setup_unnamed.py <train cell> [processes]

What ``setup.unnamed_s`` holds, stage by stage: the set-up path of
perf/run.py and kinds/train.py OUTSIDE the first calls (imports, the
backend's start, building the Program, the feeds, their device_put),
replayed with a timer around each stage, in ``processes`` fresh
processes in turn (default 3: the stages' spread is the point). The
launcher stays off jax; each child holds the chip alone. The startup
program runs (the feeds' placement needs nothing of it, the chip's
memory state does) but no eval and no train step: seconds, not minutes.
Through chiprun; every child's line also goes to
chiprun_out/setup_unnamed-<cell>.jsonl."""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def child(cell_name: str, seed: int):
    marks, last = {}, [T_START]

    def mark(name):
        now = time.perf_counter()
        marks[name] = round(now - last[0], 3)
        last[0] = now

    from perf import harness

    cell = harness.load_json("perf", "workloads", f"{cell_name}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    mark("import_harness")
    import jax  # noqa: F401
    mark("import_jax")
    devices = harness.require_tpu(cell["chips"])
    mark("backend_start")
    from paddle_tpu import jax_cache

    from perf import models

    jax_cache.configure()
    mark("import_program")
    fam = models.family(cfg)
    main, startup, evalp, loss, model = models.build_train(cfg, seed)
    mark("build_train")
    import paddle_tpu as fluid

    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    jax.block_until_ready([scope.find_var(n) for n in scope.var_names()])
    mark("(startup first call: named elsewhere)")
    feeds_np = fam.feeds(cfg, cell["traffic"], seed)
    mark("feeds")
    feeds = [{k: jax.device_put(v) for k, v in f.items()}
             for f in feeds_np]
    jax.block_until_ready(feeds)
    mark("device_put")
    exe.close()
    print(json.dumps({"cell": cell_name, "seed": seed,
                      "device": devices[0].device_kind, "stages_s": marks}),
          flush=True)


def main(argv):
    if len(argv) >= 2 and argv[0] == "--child":
        return child(argv[1], int(argv[2]))
    cell, n = argv[0], int(argv[1]) if len(argv) > 1 else 3
    out = os.path.join(ROOT, "chiprun_out", f"setup_unnamed-{cell}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for i in range(n):
        seed = 2147480000 + 7919 * (i + 1)
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", cell,
             str(seed)], capture_output=True, text=True, cwd=ROOT)
        lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
        if done.returncode or not lines:
            print(f"process {i} failed ({done.returncode}):\n"
                  f"{done.stderr[-2000:]}")
            return 1
        print(lines[-1], flush=True)
        with open(out, "a") as f:
            f.write(lines[-1] + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
