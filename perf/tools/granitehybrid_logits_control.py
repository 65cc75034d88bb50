"""python perf/tools/granitehybrid_logits_control.py --seeds a,b,c [--control-seeds a] [--set key=value ...]

The readings the Granite-4.0-H family's second check
(perf/reference/granitehybrid.second_check) sets its limit between, per
seed, on the cell's correctness sample at the published widths and at
the state a run of the cell starts from (the form of
nemotronh_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference, on every seed;
- the CONTROLS (``--control-seeds``; all seeds by default), each the same
  reference changed in ONE way and judged as if it were the program, by
  ``check_loss``'s relative difference and by the second check: both
  operands of every weight matrix multiplication rounded to float8
  (e4m3fn: the nearest precision below the bf16 the configuration trains
  in; bfloat16 beside it, which reads what the program's own rounding
  reads), the softmax scale 1 / sqrt(64) where the config states 1 / 64
  (``scale_sqrt``), the gated norm's statistics over 8 groups of 512
  where the model has one of 4096 (``norm_groups``), the state dropped at
  every boundary of the kernels' chunk (``no_carry``). Each but bfloat16
  has to come out as not correct by the second check.

``--set QK_STD_FACTOR=1`` reads them at the builder's own state (the
family's ``build_graph`` lays sharper query and key columns over the
attention layers so that ``correct`` sees the scale).

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/granitehybrid-logits-control.json and
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
    ap.add_argument("--workload", default="granite-train-s16384")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--set", nargs="*", default=[])
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
    for key, value in (kv.split("=") for kv in args.set):
        setattr(fam, key, float(value))
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds = ints(args.seeds)
    control_seeds = set(seeds if args.control_seeds is None
                        else ints(args.control_seeds))
    controls = {"float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn),
                "bfloat16": dict(round_to=jnp.bfloat16),
                **{name: dict(ablate=name) for name in ref.ABLATIONS}}
    forward = {name: jax.jit(lambda w, ids, kw=kw: ref.forward(
        w, cfg, ids, last=ref.LAST_POSITIONS, **kw))
        for name, kw in controls.items()}
    loss = {name: jax.jit(lambda w, f, kw=kw: ref.loss(w, cfg, f, **kw))
            for name, kw in {"reference": {}, **controls}.items()}

    rows = []
    for seed in seeds:
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        fetched = {"last_logits": np.asarray(exe.run(
            evalp, feed=sample, fetch_list=[model["last_logits"]],
            scope=scope)[0])}
        w = weights_from_scope(scope)
        ids = jnp.asarray(sample["input_ids"])
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            if seed in control_seeds:
                feed = {k: jnp.asarray(v) for k, v in sample.items()}
                want = float(loss["reference"](w, feed))
                for name in controls:
                    got = float(loss[name](w, feed))
                    rel = abs(got - want) / abs(want)
                    problems, record = ref.second_check(
                        w, cfg, sample,
                        {"last_logits": forward[name](w, ids)})
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

    table = {f"{side}.{key}": span(side, key)
             for side in ("program", *controls)
             for key in ("logit_err_over_rms", "loss_rel")
             if side != "program" or key != "loss_rel"}
    table["limits"] = [ref.LOGIT_ERR_LIMIT]
    table["program_correct"] = all(not r["program_problems"] for r in rows)
    table["controls_not_correct_by_second_check"] = {
        name: [r[name]["fails_second_check"] for r in rows if name in r]
        for name in controls}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "granitehybrid-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
