"""python perf/tools/keye_logits_control.py --seeds a,b,c [--control-seeds a]

The readings the Keye family's second check
(perf/reference/keye.second_check) sets its limits between, per seed, on
the cell's correctness sample at the published widths (the form of
smallthinker_logits_control.py):

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference;
- the LOWER-PRECISION control: the same reference with both operands of
  every matrix multiplication rounded to float8 (e4m3fn), the nearest
  precision below the bf16 the configuration trains in, judged as if it
  were the program. It has to come out as not correct.

And on ``--control-seeds``, each control that a piece of the mechanism is
computed at all, judged as if it were the program; every one has to come
out as not correct at the start state: no selection (dense causal
attention); the LAST 2048 positions for the indexer's choice; top-1024;
no relu; ``w`` uniform; no LayerNorm on ``kI``; the indexer's rotation
off; QK-norm off.

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/keye-logits-control.json and prints
the table. Needs a TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
READINGS = ("logit_err_over_rms", "flipped_share",
            "first_layer_below_threshold", "mean_row_diff",
            "worst_row_diff", "logit_err_under_own_selection")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="keye-train-s16384")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
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
    topk = int(cfg["sa_config"]["topk"])
    low = {"float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn)}
    pieces = {"dense": dict(select="dense"), "recent": dict(select="recent"),
              f"top-{topk // 2}": dict(select=topk // 2),
              "no_relu": dict(ablate="relu"),
              "w_uniform": dict(ablate="weights"),
              "no_knorm": dict(ablate="knorm"),
              "no_index_rope": dict(ablate="rope"),
              "no_qknorm": dict(ablate="qknorm")}
    forward = {name: jax.jit(lambda w, ids, pos, kw=kw: ref.forward(
        w, cfg, ids, pos, last=ref.LAST_POSITIONS, keep=ref.LAST_POSITIONS,
        **kw)) for name, kw in {**low, **pieces}.items()}
    with_pieces = {int(s) for s in args.control_seeds.split(",") if s}
    first, count, e = ref.held(cfg)

    def as_program(got):
        """Judged as if it were the program: the held experts' rows are
        its own choices', the selection its own."""
        return {"last_logits": got["logits"], "top_i": got["top_i"],
                "expert_rows": [np.bincount(
                    np.asarray(t).ravel(), minlength=e)[first:first + count]
                    for t in got["top_i"]],
                "last_selected": [np.asarray(mine).astype(np.int8)
                                  for mine, _ in got["kept"]]}

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
        ids, pos = (jnp.asarray(sample[k])
                    for k in ("input_ids", "position_ids"))
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in (*low, *(pieces if seed in with_pieces else ())):
                row[f"{name}_problems"], row[name] = ref.second_check(
                    w, cfg, sample, as_program(forward[name](w, ids, pos)))
        exe.close()
        del w, scope
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows if side in r]
        return [min(vals), max(vals)]

    table = {side: {key: span(side, key) for key in READINGS}
             for side in ("program", *low, *pieces)
             if any(side in r for r in rows)}
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT, ref.MARGIN,
                       ref.ROW_DIFF_LIMIT]
    table["program_correct"] = all(not r["program_problems"] for r in rows)
    table["controls_not_correct"] = {
        name: all(r[f"{name}_problems"] for r in rows
                  if f"{name}_problems" in r)
        for name in (*low, *pieces) if any(name in r for r in rows)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "keye-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
