"""python perf/tools/sdar_logits_control.py --seeds a,b,c [--mechanism-seeds a]

The two readings the SDAR family's second check
(perf/reference/sdar.second_check) sets its limits between, per seed,
on the cell's correctness sample at the published widths (the form of
laguna_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference, and ``check_loss``'s
  relative difference beside it (``loss_rel``: the loss is a sum under
  weights of 1 / p, so a few positions of nearly clean blocks carry it);
- the CONTROL: the same reference with both operands of every weight
  matrix multiplication rounded to float8 (e4m3fn, and e5m2 beside it),
  the nearest precision below the bf16 the configuration trains in,
  judged as if it were the program. It has to come out as not correct.

And once (``--mechanism-seeds``), the control that the mask's fourth
quadrant is shut at all: the reference whose clean half SEES the noised
half (``leak``), judged as if it were the program by ``check_loss``'s
relative difference and by the second check. It has to come out as not
correct by one of them.

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/sdar-logits-control.json and
prints the table. Needs a TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
READINGS = ("logit_err_over_rms", "flipped_share")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sdar-train-s4096")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mechanism-seeds", default="")
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
    controls = {"float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn),
                "float8_e5m2": dict(round_to=jnp.float8_e5m2)}
    mechanisms = {"leak": {"leak": True}}
    mechanism_seeds = {int(s) for s in args.mechanism_seeds.split(",") if s}
    forward = {name: jax.jit(
        lambda w, ids, kw=kw: ref.forward(
            w, cfg, ids, last=ref.LAST_POSITIONS, **kw))
        for name, kw in {**controls, **mechanisms}.items()}
    loss = {name: jax.jit(lambda w, f, kw=kw: ref.loss(w, cfg, f, **kw))
            for name, kw in {"reference": {}, **mechanisms}.items()}
    first, count, e = ref.held(cfg)

    def as_program(got):
        """Judged as if it were the program: the held experts' rows are
        its own choices'."""
        return {"last_logits": got["logits"], "top_i": got["top_i"],
                "expert_rows": [np.bincount(
                    np.asarray(t).ravel(), minlength=e)[first:first + count]
                    for t in got["top_i"]]}

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        fetch, shape = jax.tree.flatten(
            {k: model[k] for k in fam.CHECK_FETCH})
        *got, prog_loss = exe.run(evalp, feed=sample, scope=scope,
                                  fetch_list=fetch + [model["loss"]])
        fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in got])
        prog_loss = float(np.asarray(prog_loss))
        w = weights_from_scope(scope)
        ids = jnp.asarray(sample["input_ids"])
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            # check_loss's own reading, beside the second check's
            feed = {k: jnp.asarray(v) for k, v in sample.items()}
            want = float(loss["reference"](w, feed))
            program["loss_rel"] = abs(prog_loss - want) / abs(want)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in controls:
                row[f"{name}_problems"], row[name] = ref.second_check(
                    w, cfg, sample, as_program(forward[name](w, ids)))
            if seed in mechanism_seeds:
                for name in mechanisms:
                    got = float(loss[name](w, feed))
                    rel = abs(got - want) / abs(want)
                    problems, record = ref.second_check(
                        w, cfg, sample, as_program(forward[name](w, ids)))
                    row[name] = dict(
                        record, loss=got, reference_loss=want, loss_rel=rel,
                        fails_check_loss=bool(rel > train.LOSS_REL_TOL),
                        fails_second_check=bool(problems))
        exe.close()
        del w, scope
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows]
        return [min(vals), max(vals)]

    table = {side: {key: span(side, key) for key in READINGS}
             for side in ("program", *controls)}
    table["program"]["loss_rel"] = span("program", "loss_rel")
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    table["program_correct"] = all(not r["program_problems"] for r in rows)
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows) for name in controls}
    table["mechanisms_not_correct"] = {
        name: [r[name]["fails_check_loss"] or r[name]["fails_second_check"]
               for r in rows if name in r] for name in mechanisms}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "sdar-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
