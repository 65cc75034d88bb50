"""python perf/tools/joyai_logits_control.py --seeds a,b,c

The two readings the JoyAI family's second check
(perf/reference/joyai.second_check) sets its limits between, per seed,
on the cell's correctness sample at the published widths (the form of
olmoe_logits_control.py; this family's reference takes the labels too,
the MTP module's second input, and judges two sets of logits):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference;
- the CONTROL: the same reference with both operands of every weight
  matrix multiplication rounded to float8 (e4m3fn, and e5m2 beside it),
  the nearest precision below the bf16 the configuration trains in,
  judged as if it were the program. It has to come out as not correct.

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/joyai-logits-control.json and prints
the table. Needs a TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
READINGS = ("logit_err_over_rms", "mtp_logit_err_over_rms", "flipped_share")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="joyai-train-s4096")
    ap.add_argument("--seeds", required=True)
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
    controls = {"float8_e4m3fn": jnp.float8_e4m3fn,
                "float8_e5m2": jnp.float8_e5m2}
    forward = {name: jax.jit(
        lambda w, ids, lbl, dt=dt: ref.forward(
            w, cfg, ids, lbl, round_to=dt, last=ref.LAST_POSITIONS))
        for name, dt in controls.items()}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        fetch, shape = jax.tree.flatten(
            {k: model[k] for k in fam.CHECK_FETCH})
        fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
            evalp, feed=sample, fetch_list=fetch, scope=scope)])
        w = weights_from_scope(scope)
        ids, lbl = (jnp.asarray(sample[k]) for k in ("input_ids", "labels"))
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in controls:
                got = forward[name](w, ids, lbl)
                # judged as if it were the program: the held experts'
                # rows are its own choices'
                first, count, e = ref.held(cfg)
                as_program = {
                    "last_logits": got["logits"],
                    "mtp_last_logits": got["mtp_logits"],
                    "top_i": got["top_i"],
                    "expert_rows": [np.bincount(
                        np.asarray(t).ravel(), minlength=e)[
                            first:first + count] for t in got["top_i"]]}
                row[f"{name}_problems"], row[name] = ref.second_check(
                    w, cfg, sample, as_program)
        exe.close()
        del w, scope
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows]
        return [min(vals), max(vals)]

    table = {side: {key: span(side, key) for key in READINGS}
             for side in ("program", *controls)}
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows) for name in controls}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "joyai-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
