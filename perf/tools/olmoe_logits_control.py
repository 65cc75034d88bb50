"""python perf/tools/olmoe_logits_control.py [--workload olmoe-train-s4096] --seeds a,b,c

The two readings the OLMoE family's second check
(perf/reference/olmoe.second_check) sets its limits between, per seed,
on the cell's correctness sample at the published widths:

- the PROGRAM (eval clone under bf16 AMP, as kinds/train.check_second
  fetches it) against the float32 reference;
- the CONTROL: the same reference with both operands of every matrix
  multiplication rounded to float8 (e4m3fn, and e5m2 beside it), the
  nearest precision below the bf16 the configuration trains in, judged
  as if it were the program. It has to come out as not correct.

One process; the weights are drawn from each seed by a startup program
as a run's are. Writes chiprun_out/olmoe-logits-control.json and prints
the table. Needs a TPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-train-s4096")
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
    last = ref.LAST_POSITIONS
    controls = {"float8_e4m3fn": jnp.float8_e4m3fn,
                "float8_e5m2": jnp.float8_e5m2}
    forward = {None: jax.jit(lambda w, ids: ref.forward(w, cfg, ids,
                                                        last=last))}
    for name, dt in controls.items():
        forward[name] = jax.jit(
            lambda w, ids, dt=dt: ref.forward(w, cfg, ids, round_to=dt,
                                              last=last))
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
        ids = jnp.asarray(sample["input_ids"])
        with jax.default_matmul_precision("highest"):
            problems, program = ref.second_check(w, cfg, sample, fetched)
            want = forward[None](w, ids)
            row = {"seed": seed, "program": program,
                   "program_problems": problems}
            for name in controls:
                got = forward[name](w, ids)
                row[name] = ref.compare(cfg, want, got["logits"],
                                        got["top_i"])
        exe.close()
        del w, scope
        rows.append(row)
        harness.say(f"control: {json.dumps(row)}")

    def span(side, key):
        vals = [r[side][key] for r in rows]
        return [min(vals), max(vals)]

    table = {side: {key: span(side, key)
                    for key in ("logit_err_over_rms", "flipped_share")}
             for side in ("program", *controls)}
    table["limits"] = [ref.LOGIT_ERR_LIMIT, ref.FLIP_LIMIT]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "olmoe-logits-control.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
