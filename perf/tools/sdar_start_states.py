"""python perf/tools/sdar_start_states.py --states family,fresh,t1-o-l-r0.2 [--runs 6]

What each part of the state `sdar-train-s4096` starts from
(perf/families/sdar.build_graph) is worth to the cell's spread: the
cell's untraced run (perf/run.py, --seconds 20) under each named START
STATE over the same seeds, one process a run, in turn. Per run: tokens/s,
`correct`, and the second check's two readings and the held experts'
share of the rows (perf/reference/sdar.second_check); per state: the
median, the spread as the contract reads it (perf/stats.spread) and the
readings' ranges. Lines go to chiprun_out/sdar-start-states.jsonl. Run it
through chiprun, all states of one comparison in one call (a run is
about a minute once the machine's compile cache holds the step: every
state runs the same train step, only the startup program differs).

A state is ``family`` (what the tree's family file lays, untouched),
``fresh`` (the builder's model, paddle_tpu.models.sdar.build, nothing
laid over it) or parts joined by ``-``, each laid over the builder's
model in the startup program:

    t<std>    the embedding table drawn at normal(0, std)     (builder: 0.02)
    g<mean>   every q / k norm's gains at normal(mean, mean / 10)   (1)
    o         every router's columns made orthogonal to the mask token's
              row of the table, W <- W - m^T (m W) / (m m^T)
    l         every router's columns brought to one length, r sqrt(d)
    r<std>    a router's entries' size: the length ``l`` levels to, or,
              without ``l``, a factor std / 0.02 on the drawn columns

so `t1-g2-o-l-r0.2` is the family's own state (the same weights: a test
holds the two to each other) and `o-l-r0.2` the routers' part of it
alone. PERF.md section 6, PR 61, has the states read so far."""

import argparse
import ast
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
CELL = "sdar-train-s4096"
DRAWN = 0.02   # the builder's std of every matrix
READINGS = ("logit_err_over_rms", "flipped_share", "held_row_share")


def parse(state):
    parts = dict(table=DRAWN, gain=None, orthogonal=False, level=False,
                 router=DRAWN)
    for p in state.split("-") if state != "fresh" else ():
        if p == "o":
            parts["orthogonal"] = True
        elif p == "l":
            parts["level"] = True
        elif p[0] == "t":
            parts["table"] = float(p[1:])
        elif p[0] == "g":
            parts["gain"] = (float(p[1:]), float(p[1:]) / 10)
        elif p[0] == "r":
            parts["router"] = float(p[1:])
        else:
            raise SystemExit(f"no such part of a start state: {p!r}")
    return parts


def lay(pcfg, is_test, table, gain, orthogonal, level, router):
    """The builder's model with the parts laid over it in the startup
    program (a second write behind the builder's: the later one stands,
    and the draws in front of it stay what they were)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import sdar as M

    model = M.build(pcfg, is_test=is_test, embedding_init_std=table)
    startup = fluid.default_startup_program().global_block()
    routers = [n for n in startup.vars if n.endswith("_moe_router.w")]
    if gain:
        for name, var in list(startup.vars.items()):
            if name.endswith(("_qnorm.scale", "_knorm.scale")):
                NormalInitializer(*gain)(var, startup)
    with fluid.program_guard(fluid.default_startup_program()):
        row = layers.gather(startup.var(M.TABLE), layers.assign(
            np.array([pcfg.mask_token_id], np.int64)))          # m [1, d]
        inv = layers.pow(layers.matmul(row, row, transpose_y=True), -1.0)
        for name in routers:
            w = new = startup.var(name)
            if orthogonal:
                shared = layers.matmul(row, layers.matmul(row, w),
                                       transpose_x=True)        # m^T (m W)
                new = layers.elementwise_sub(
                    w, layers.elementwise_mul(shared, inv))
            if level:
                length = layers.pow(layers.reduce_sum(
                    layers.elementwise_mul(new, new), dim=0,
                    keep_dim=True), -0.5)
                new = layers.elementwise_mul(new, layers.scale(
                    length, scale=router * pcfg.hidden_size ** 0.5))
            elif router != DRAWN:
                new = layers.scale(new, scale=router / DRAWN)
            if new is not w:
                layers.assign(new, output=w)
    return model


def one(state, seed, seconds):
    """This process IS the run: perf/run.py with the family's
    ``build_graph`` standing for ``state``."""
    if state != "family":
        from perf.families import sdar as fam

        fam.build_graph = lambda pcfg, is_test=False: lay(
            pcfg, is_test, **parse(state))
    from perf import run

    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", "0"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--states", default="family")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2147487919)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--one", help="(a child's: the state it runs)")
    ap.add_argument("--seed", type=int)
    args = ap.parse_args()
    if args.one:
        return one(args.one, args.seed, args.seconds)

    from perf import stats   # (no jax: the children take the chip in turn)

    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/sdar-start-states.jsonl", "a")
    table = {}
    for state in args.states.split(","):
        if state != "family":
            parse(state)   # (a misspelt part stops the call here)
        rows = table[state] = []
        for i in range(args.runs):
            seed = args.first_seed + 7919 * i
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", state,
                 "--seed", str(seed), "--seconds", str(args.seconds)],
                capture_output=True, text=True)
            lines = done.stdout.splitlines()
            last = [ln for ln in lines if ln.startswith("{")][-1:]
            check = [ln.split("second check: ", 1)[1] for ln in lines
                     if "second check: " in ln][-1:]
            row = {"state": state, "seed": seed, "rc": done.returncode,
                   "line": json.loads(last[0]) if last else None,
                   "second": ast.literal_eval(check[0]) if check else None}
            out.write(json.dumps(row) + "\n")
            out.flush()
            if not last:
                print(f"STATE {state} seed {seed} rc {done.returncode}: no "
                      f"line\n{done.stdout[-1500:]}\n{done.stderr[-1500:]}",
                      flush=True)
                break
            rows.append(row)
            print(f"STATE {state} seed {seed} tokens/s "
                  f"{row['line']['metrics']['train_tokens_per_s']['value']} "
                  f"correct {row['line']['correct']} second "
                  f"{[row['second'] and row['second'][k] for k in READINGS]}"
                  f" {row['line']['problems'] or ''}", flush=True)
    for state, rows in table.items():
        rate = [r["line"]["metrics"]["train_tokens_per_s"]["value"]
                for r in rows]
        if len(rate) < 2:
            continue
        between = {k: [f(r["second"][k] for r in rows) for f in (min, max)]
                   for k in READINGS if all(r["second"] for r in rows)}
        print(f"STATE {state}: {len(rate)} runs, median "
              f"{statistics.median(rate):.1f}, {min(rate):.1f} .. "
              f"{max(rate):.1f}, spread {stats.spread(rate) * 100:.3f}%, "
              f"correct {sum(r['line']['correct'] for r in rows)}; "
              f"{between}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
