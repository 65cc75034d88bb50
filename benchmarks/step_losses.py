"""The losses of a train cell's first steps, as the bits of their float32,
from one checkout or from two held against each other:

    chiprun -- python benchmarks/step_losses.py --cells keye-train-s16384 \
        sdar-train-s4096 --against .parent [--steps 6] [--seed N]

A change that leaves every operation of a program and their order alone
(another tile, another grid, another place for the same arithmetic)
trains on the parent's losses to the bit; perf/run.py prints a window's
first and last loss to four decimals. A cell's program is built, started
and fed as perf/kinds/train.py does (the checkout's own perf/ and
paddle_tpu/: each side is a process of its own with that checkout as
its directory; this process stays off jax and the chip), ``--steps``
steps run, and every loss printed as ``float.hex``. With ``--against
DIR`` both sides run in turn and the lines say ``equal`` or at which
step they part; the table goes to chiprun_out/step_losses.json. Needs a
TPU (a cell's program is the chip's).
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_side(cell_name, seed, steps, overrides=(), seq_len=None):
    """This directory's checkout: the cell's losses as float32 hex.
    ``overrides`` ("key=value": an integer where it reads as one, else
    the string) are laid over the configuration file, ``seq_len`` over
    the cell's row (``--set recompute=none --seq-len 4096``: a marked
    cell's step without its marks, at a row where both fit)."""
    sys.path[0] = os.getcwd()
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import jax_cache
    from perf import harness, models

    cell = harness.load_json("perf", "workloads", f"{cell_name}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    for key, value in (kv.split("=", 1) for kv in overrides):
        cfg[key] = int(value) if value.lstrip("-").isdigit() else value
    if seq_len:
        cell["traffic"].update(seq_len=seq_len, real_len=[seq_len, seq_len])
    harness.require_tpu(cell["chips"])
    jax_cache.configure()
    fam = models.family(cfg)
    main, startup, _, loss, _ = models.build_train(cfg, seed)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    feeds = [{k: jax.device_put(v) for k, v in f.items()}
             for f in fam.feeds(cfg, cell["traffic"], seed)]
    losses = [exe.run(main, feed=feeds[i % len(feeds)], fetch_list=[loss],
                      scope=scope, return_numpy=False)[0]
              for i in range(steps)]
    return [float(np.asarray(x, np.float32).reshape(())).hex()
            for x in jax.device_get(losses)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", required=True)
    ap.add_argument("--against", default=None, metavar="DIR")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=2147491927)
    ap.add_argument("--one-side", action="store_true")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE")
    ap.add_argument("--seq-len", type=int, default=None)
    args = ap.parse_args()
    if args.one_side:
        print(json.dumps(one_side(args.cells[0], args.seed, args.steps,
                                  args.set, args.seq_len)))
        return 0

    def side(directory, cell):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one-side",
             "--cells", cell, "--steps", str(args.steps), "--seed",
             str(args.seed), "--set", *args.set]
            + (["--seq-len", str(args.seq_len)] if args.seq_len else []),
            cwd=directory, capture_output=True, text=True)
        if out.returncode:
            print(out.stderr[-2000:], file=sys.stderr)
            raise SystemExit(f"{directory} {cell}: exit {out.returncode}")
        return json.loads(out.stdout.strip().splitlines()[-1])

    table, same = {}, True
    for cell in args.cells:
        row = {"seed": args.seed, "change": side(ROOT, cell)}
        said = " ".join(row["change"])
        if args.against:
            row["parent"] = side(os.path.join(ROOT, args.against), cell)
            parted = [i for i, (a, b) in enumerate(
                zip(row["change"], row["parent"])) if a != b]
            row["equal"] = not parted
            same = same and not parted
            said += (" equal to the parent's" if not parted else
                     f" PART from the parent's at step {parted[0]}: "
                     + " ".join(row["parent"]))
        table[cell] = row
        print(cell, said, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step_losses.json"),
              "w") as f:
        json.dump(table, f, indent=1)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
