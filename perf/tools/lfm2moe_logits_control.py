"""python perf/tools/lfm2moe_logits_control.py --seeds a,b,c [--control-seeds a] [--ablation-seeds a]

The two readings the LFM2-MoE family's second check
(perf/reference/lfm2moe.second_check) sets its limits between, per
seed, on the cell's correctness sample at the published widths (the form
of nemotronh_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference, on every seed;
- the CONTROL (``--control-seeds``; all seeds by default): the same
  reference with both operands of every weight matrix multiplication
  rounded to float8 (e4m3fn, and e5m2 beside it), the nearest precision
  below the bf16 the configuration trains in, judged as if it were the
  program. It has to come out as not correct.

And (``--ablation-seeds``) ONE ablation a new mechanism
(``reference/lfm2moe.ABLATIONS``): the B gate dropped, the C gate
dropped, the convolution cut to its last tap, the taps reversed, the
per-head QK-norm dropped, the selection bias ignored in the choice; each
judged as if it were the program by ``check_loss``'s relative difference
and by the second check. Each has to come out as not correct by at
least one of them, or the initialisation is hiding it: the state is the
one a run of the cell starts from, in which the family's startup
program holds the QK-norms' gains and ``expert_bias`` where both act
(perf/families/lfm2moe.py).

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/lfm2moe-logits-control.json and
prints the table. Needs a TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2moe-train-s8192")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--ablation-seeds", default="")
    args = ap.parse_args()

    from perf import harness, models

    cell = harness.load_json("perf", "workloads", f"{args.workload}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    harness.require_tpu(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import jax_cache
    from perf.kinds import train
    from perf.reference.common import weights_from_scope

    jax_cache.configure()
    fam, ref = models.family(cfg), models.reference(cfg)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds = ints(args.seeds)
    control_seeds = set(seeds if args.control_seeds is None
                        else ints(args.control_seeds))
    ablation_seeds = set(ints(args.ablation_seeds))
    controls = {"float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn),
                "float8_e5m2": dict(round_to=jnp.float8_e5m2)}
    ablations = {name: dict(ablate=name) for name in ref.ABLATIONS}
    forward = {name: jax.jit(lambda w, ids, kw=kw: ref.forward(
        w, cfg, ids, last=ref.LAST_POSITIONS, **kw))
        for name, kw in {**controls, **ablations}.items()}
    loss = {name: jax.jit(lambda w, f, kw=kw: ref.loss(w, cfg, f, **kw))
            for name, kw in {"reference": {}, **ablations}.items()}

    def as_program(w, sample, fetched, out):
        """The second check of a reference's ``out`` judged as if it
        were the program's fetch."""
        return ref.second_check(w, cfg, sample, dict(
            fetched, last_logits=out["logits"],
            top_i=[np.asarray(t) for t in out["top_i"]]))

    rows = []
    for seed in seeds:
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        fetch, shape = jax.tree.flatten(
            {k: model[k] for k in fam.CHECK_FETCH})
        # (at the program's own matmul precision: never inside the
        # ``highest`` the reference needs, under which the kernels' bf16
        # products do not lower)
        fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
            evalp, feed=sample, fetch_list=fetch, scope=scope)])
        w = weights_from_scope(scope)
        ids = jnp.asarray(sample["input_ids"])
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in controls if seed in control_seeds else ():
                row[f"{name}_problems"], row[name] = as_program(
                    w, sample, fetched, forward[name](w, ids))
            if seed in ablation_seeds:
                feed = {k: jnp.asarray(v) for k, v in sample.items()}
                want = float(loss["reference"](w, feed))
                for name in ablations:
                    got = float(loss[name](w, feed))
                    rel = abs(got - want) / abs(want)
                    problems, record = as_program(
                        w, sample, fetched, forward[name](w, ids))
                    row[name] = dict(
                        record, loss=got, reference_loss=want, loss_rel=rel,
                        fails_check_loss=bool(rel > train.LOSS_REL_TOL),
                        fails_second_check=bool(problems))
        exe.close()
        del w, scope
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows if side in r]
        return [min(vals), max(vals), len(vals)] if vals else None

    sides = ("program", *controls, *ablations)
    table = {f"{side}.{key}": span(side, key) for side in sides
             for key in ("logit_err_over_rms", "flipped_share")}
    for key in ("held_row_share", "max_expert_load"):
        table[f"program.{key}"] = span("program", key)
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    table["program_correct"] = all(not r["program_problems"] for r in rows)
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows
                  if f"{name}_problems" in r) for name in controls}
    for check in ("check_loss", "second_check"):
        table[f"ablations_not_correct_by_{check}"] = {
            name: [r[name][f"fails_{check}"] for r in rows if name in r]
            for name in ablations}
    table["ablations.loss_rel"] = {
        name: [r[name]["loss_rel"] for r in rows if name in r]
        for name in ablations}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "lfm2moe-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
