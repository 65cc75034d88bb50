"""python perf/tools/xing4_logits_control.py --seeds a,b,c [--control-seeds a,b] [--mechanism-seeds a]

The readings the Xing4.0 family's second check
(perf/reference/xing4.second_check) sets its limits between, per seed,
on the cell's correctness sample at the published widths (the form of
kimilinear_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference;
- the PRECISION controls (``--control-seeds``, default all): the same
  reference with both operands of every weight matrix multiplication
  (the mixes' projections too) rounded to float8 (e4m3fn, and e5m2
  beside it), the nearest precision below the bf16 the configuration
  trains in, judged as if it were the program;
- the MECHANISM controls (``--mechanism-seeds``, default the first
  seed): the reference in full float32 with one piece left out or done
  wrong (``reference/xing4.CONTROLS``: one Sinkhorn iteration for
  twenty, no column step, H_post without its 2, the mix from stream 0
  alone, the softmax scale without mscale^2, the plain rotary table),
  at the state a run starts from (``families/xing4.build_graph``); and
  ``no_clamp`` at a second state whose H_res bias is drawn
  ``--wide-bias`` wide (25: entries beyond +-30), where the program is
  read again too.

Each control has to come out as not correct. One process; the weights
are drawn from each seed by a startup program as a run's are. Writes
chiprun_out/xing4-logits-control.json and prints the table. Needs a
TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
READINGS = ("logit_err_over_rms", "flipped_share")
PRECISIONS = ("float8_e4m3fn", "float8_e5m2")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="xing4-train-s4096")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds")
    ap.add_argument("--mechanism-seeds")
    ap.add_argument("--wide-bias", type=float, default=25.0)
    ap.add_argument("--set", nargs="*", default=[],
                    help="NAME=float laid over the family module "
                         "(HC_ALPHA=0.01 LATENT_QUERY_STD=0.02 "
                         "HC_RES_BIAS_STD=0: nearer the builder's state)")
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
    controls = {p: dict(round_to=getattr(jnp, p)) for p in PRECISIONS}
    controls.update({c: dict(control=c) for c in ref.CONTROLS})
    forward = {name: jax.jit(lambda w, ids, lbl, kw=kw: ref.forward(
        w, cfg, ids, lbl, last=ref.LAST_POSITIONS, **kw))
        for name, kw in controls.items()}
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = ([int(s) for s in args.control_seeds.split(",")]
                     if args.control_seeds else seeds)
    mechanism_seeds = ([int(s) for s in args.mechanism_seeds.split(",")]
                       if args.mechanism_seeds else seeds[:1])

    def readings(seed, names):
        """The program's record and each named control's at the family
        module's state as it stands."""
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
            row = {"program": program, "program_problems": problems}
            for name in names:
                got = forward[name](w, ids, lbl)
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
        return row

    rows = []
    for seed in seeds:
        names = [p for p in PRECISIONS if seed in control_seeds]
        if seed in mechanism_seeds:
            names += [c for c in ref.CONTROLS if c != "no_clamp"]
        row = dict(readings(seed, names), seed=seed)
        if seed in mechanism_seeds:
            std, fam.HC_RES_BIAS_STD = fam.HC_RES_BIAS_STD, args.wide_bias
            wide = readings(seed, ["no_clamp"])
            fam.HC_RES_BIAS_STD = std
            row.update(no_clamp=wide["no_clamp"],
                       no_clamp_problems=wide["no_clamp_problems"],
                       program_wide_bias=wide["program"],
                       program_wide_bias_problems=wide["program_problems"])
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows if side in r]
        return [min(vals), max(vals)] if vals else None

    table = {side: {key: span(side, key) for key in READINGS}
             for side in ("program", "program_wide_bias", *controls)}
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    table["set"] = args.set
    table["program_correct"] = not any(
        r["program_problems"] or r.get("program_wide_bias_problems")
        for r in rows)
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows if name in r)
        for name in controls}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "xing4-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
