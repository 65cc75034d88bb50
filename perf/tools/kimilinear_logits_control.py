"""python perf/tools/kimilinear_logits_control.py --seeds a,b,c [--control-seeds a,b]

The readings the Kimi Linear family's second check
(perf/reference/kimilinear.second_check) sets its limits between, per
seed, on the cell's correctness sample at the published widths (the form
of joyai_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference, whose delta rule is a scan
  over the row's 4096 positions;
- the CONTROLS (``--control-seeds``, default all), each the same
  reference judged as if it were the program, each has to come out as
  not correct: both operands of every weight matrix multiplication
  rounded to float8 (e4m3fn, and e5m2 beside it), the nearest precision
  below the bf16 the configuration trains in; and ``rotated``, the
  reference in full float32 with the 64 shared key features and the
  queries' last 64 turned by RoPE at the published rope_theta, which
  this model does not do (mla_use_nope).

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/kimilinear-logits-control.json and
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
    ap.add_argument("--workload", default="kimilinear-train-s4096")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds")
    ap.add_argument("--controls", default="float8_e4m3fn,float8_e5m2,rotated")
    ap.add_argument("--set", nargs="*", default=[],
                    help="NAME=float laid over the family module "
                         "(LATENT_QUERY_STD=0.02: the builder's own state)")
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
    for name, value in (kv.split("=") for kv in args.set):
        assert hasattr(fam, name), name
        setattr(fam, name, float(value))
    controls = {"float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn),
                "float8_e5m2": dict(round_to=jnp.float8_e5m2),
                "rotated": dict(rotate=float(cfg["rope_theta"]))}
    controls = {k: controls[k] for k in args.controls.split(",")}
    forward = {name: jax.jit(lambda w, ids, kw=kw: ref.forward(
        w, cfg, ids, last=ref.LAST_POSITIONS, **kw))
        for name, kw in controls.items()}
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = ([int(s) for s in args.control_seeds.split(",")]
                     if args.control_seeds else seeds)
    rows = []
    for seed in seeds:
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        fetch, shape = jax.tree.flatten(
            {k: model[k] for k in fam.CHECK_FETCH})
        fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
            evalp, feed=sample, fetch_list=fetch, scope=scope)])
        w = weights_from_scope(scope)
        ids = jnp.asarray(sample["input_ids"])
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in controls if seed in control_seeds else ():
                got = forward[name](w, ids)
                # judged as if it were the program: the held experts'
                # rows are its own choices'
                first, count, e = ref.held(cfg)
                as_program = {
                    "last_logits": got["logits"], "top_i": got["top_i"],
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
        vals = [r[side][key] for r in rows if side in r]
        return [min(vals), max(vals)] if vals else None

    table = {side: {key: span(side, key) for key in READINGS}
             for side in ("program", *controls)}
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    table["set"] = args.set
    table["program_correct"] = not any(r["program_problems"] for r in rows)
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows if name in r)
        for name in controls}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "kimilinear-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
