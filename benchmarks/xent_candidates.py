"""Time the candidates for the loss head alone on the chip, at the calls
of the ten train cells: a Program that is only a head (ids -> a small
table -> final_norm -> the projection to the vocabulary ->
softmax_with_cross_entropy -> mean, bf16 AMP, Adam: chip_smoke's
``loss_head_program``), so the loss op meets what it meets in a cell:
bf16 logits out of a ``mul``, its gradient into the two ``mul_grad``
matmuls, Adam behind the weight's.

    chiprun -- python benchmarks/xent_candidates.py [--calls olmoe ...]
    python benchmarks/xent_candidates.py --dump DIR [--calls ...]   (no chip)

P the composition the op was before (a float32 log_softmax, the label's
log-probability by ``take_along_axis``, Softmax = exp of it) under the
generic grad op ``core/autodiff.make_grad_compute`` derives for it (a
zero cotangent through the unread Softmax); N the op and its own grad
op (ops/nn_ops.py); NB the same with dlogits behind a
``jax.lax.optimization_barrier`` (one bf16 materialisation that both
gradient matmuls read, where XLA otherwise clones the chain that makes
dlogits into each). P and NB are op types this file registers; N is
the tree's. Each is held to P's loss and gradients on one batch first;
then ms a call twice over: [the median wall time of ``Executor.run``
(host clock, the loss fetched), the chip's busy time a call over 5
traced calls (the union of the trace's ``XLA Ops`` events)], and the
five longest device ops of a call. The table goes to
chiprun_out/xent_candidates.json. ``--dump DIR`` needs no chip: it
compiles every form for a described v5e, writes the compiled modules
to DIR and lists of each the fusions that touch a [tokens, vocab]
tensor: exponentials, gathers, matmuls, float32 results of that size,
and ``memory_analysis()``'s temporaries.
"""

import argparse
import json
import math
import os
import re
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "xent_candidates.json")
# call: (batch, positions, width, vocabulary, soft labels); a chip's rows
CALLS = {
    "olmoe": (2, 4096, 2048, 50304, False),
    "smallthinker": (1, 16384, 2560, 18992, False),
    "qwen3next": (1, 8192, 2048, 18992, False),
    "phi4flash": (1, 4096, 2560, 25008, False),
    "joyai": (1, 4096, 2048, 16160, False),       # twice a step (MTP)
    "nemotron3nano": (1, 4096, 2688, 16384, False),
    "lfm2moe": (1, 8192, 2048, 8192, False),
    "bert": (256, 128, 768, 30522, False),
    "tbase": (128, 256, 512, 10000, True),
    "tbase-dp4": (32, 256, 512, 10000, True),
}
FORMS = ("P", "N", "NB")


def old_composition(ins, attrs):
    """softmax_with_cross_entropy as it stood before it got a grad op of
    its own (kept here, for this file's form P and for
    tests/test_loss_head.py, not in the op): the float32
    log-probabilities whole, the label's by ``take_along_axis``."""
    import jax
    import jax.numpy as jnp

    logits, label = ins["Logits"][0], ins["Label"][0]
    ignore_index = attrs.get("ignore_index", -100)
    logp = jax.nn.log_softmax(
        logits.astype(jnp.promote_types(logits.dtype, jnp.float32)), axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lbl = label
        if jnp.ndim(lbl) == jnp.ndim(logits):
            lbl = jnp.squeeze(lbl, axis=-1)
        lbl_i = lbl.astype(jnp.int32)
        loss = -jnp.take_along_axis(
            logp, jnp.maximum(lbl_i, 0)[..., None], axis=-1)
        if ignore_index >= 0:
            mask = (lbl_i != ignore_index)[..., None]
            loss = loss * mask.astype(loss.dtype)
    return {"Softmax": [jnp.exp(logp)], "Loss": [loss]}


def register_forms():
    """The two op types beside the tree's: ``xent_parent``
    (``old_composition`` under the generic grad op) and ``xent_barrier``
    (the tree's pair, dlogits held)."""
    import jax

    from paddle_tpu.core.registry import has_op, register_op
    from paddle_tpu.ops import nn_ops

    if has_op("xent_parent"):
        return
    register_op("xent_parent", diff_inputs=("Logits",))(old_composition)

    def maker(*args):
        return [dict(d, type="xent_barrier_grad") for d in
                nn_ops._softmax_with_cross_entropy_grad_maker(*args)]

    register_op("xent_barrier", diff_inputs=("Logits",), grad_maker=maker)(
        nn_ops._softmax_with_cross_entropy)

    @register_op("xent_barrier_grad", no_grad=True)
    def _held(ins, attrs):
        outs = nn_ops._softmax_with_cross_entropy_grad(ins, attrs)
        return {k: [jax.lax.optimization_barrier(v[0])]
                for k, v in outs.items()}


def xent_of(form):
    """layers.softmax_with_cross_entropy for the tree's pair, the same
    layer over the form's op type for the two others."""
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    if form == "N":
        return layers.softmax_with_cross_entropy
    op_type = {"P": "xent_parent", "NB": "xent_barrier"}[form]

    def xent(logits, label, soft_label=False):
        helper = LayerHelper(op_type)
        softmax = helper.create_variable_for_type_inference(logits.dtype)
        loss = helper.create_variable_for_type_inference(logits.dtype)
        helper.append_op(
            op_type, inputs={"Logits": logits, "Label": label},
            outputs={"Softmax": softmax, "Loss": loss},
            attrs={"soft_label": soft_label, "ignore_index": -100})
        return loss

    return xent


def fusions(text, tokens, vocab):
    """One row a fused computation of the compiled module that holds a
    tensor of the logits' size (tokens x vocab elements, in whatever
    layout): its exponentials, gathers and matmuls, its ROOT, and
    whether that writes a float32 tensor of the size."""
    from perf import trace

    def sized(s, dtype=r"[a-z0-9]+"):
        return any(math.prod(map(int, dims.split(","))) == tokens * vocab
                   for dims in re.findall(dtype + r"\[(\d+(?:,\d+)*)\]", s))

    rows, name, body = [], None, []
    for line in text.splitlines():
        head = re.match(r"^%?(fused_computation[\w.\-]*) \(.*\{\s*$", line)
        if head:
            name, body = head.group(1), []
        elif name and line.startswith("}"):
            if any(sized(ln) for ln in body):
                root = next(ln.strip() for ln in body if "ROOT " in ln)
                result = trace.parse(root.replace("ROOT ", ""))[2]
                rows.append({
                    "computation": name,
                    "exp": sum(" exponential(" in ln for ln in body),
                    "gather": sum(" gather(" in ln for ln in body),
                    "matmul": sum(" convolution(" in ln or " dot(" in ln
                                  for ln in body),
                    "writes_f32_logits": sized(result, "f32"),
                    "root": re.sub(r", metadata=.*", "", root)[:200]})
            name = None
        elif name:
            body.append(line)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    ap.add_argument("--forms", nargs="*", default=list(FORMS))
    ap.add_argument("--dump")
    args = ap.parse_args()

    import jax
    import numpy as np

    import chip_smoke
    import paddle_tpu as fluid
    from perf import trace

    sharding = None
    if args.dump:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        sharding = jax.sharding.SingleDeviceSharding(
            topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.default_backend = lambda: "tpu"
        os.makedirs(args.dump, exist_ok=True)
    elif jax.default_backend() != "tpu":
        print("xent_candidates: no TPU (--dump DIR compiles without one)",
              file=sys.stderr)
        return 2
    register_forms()

    def timed(exe, main, feeds, loss, scope):
        def call():
            return exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)

        call()
        took = []
        for _ in range(12):
            t0 = time.perf_counter()
            call()
            took.append((time.perf_counter() - t0) * 1e3)
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                for _ in range(5):
                    call()
            doc = trace.load(trace.find_xplane(d))
        ops = [ev for plane in doc["planes"][:1] for line in plane["lines"]
               if line["name"] == trace.OPS_LINE for ev in line["events"]]
        busy = trace.union_ns([(t, t + dur) for _, t, dur in ops]) / 5e6
        by_op = {}
        for name, self_ns in trace.self_times(ops):
            op = trace.label(name)
            by_op[op] = by_op.get(op, 0.0) + self_ns / 5e6
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:5]
        return ([round(statistics.median(took), 3), round(busy, 3)],
                {k: round(v, 3) for k, v in top})

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    table = []
    for name in args.calls:
        batch, seq, width, vocab, soft = CALLS[name]
        row = {"call": name, "shape": [batch * seq, width, vocab],
               "labels": "soft" if soft else "hard"}
        want = None
        for form in args.forms:
            main, startup, loss, feeds = chip_smoke.loss_head_program(
                batch, seq, width, vocab, soft=soft, xent=xent_of(form))
            try:
                if args.dump:
                    compiled = chip_smoke.lower_train_step(
                        main, loss, seq, batch, sharding).compile()
                    text = compiled.as_text()
                    with open(os.path.join(
                            args.dump, f"{name}.{form}.txt"), "w") as f:
                        f.write(text)
                    mem = compiled.memory_analysis()
                    row[form] = {
                        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
                        "fusions": fusions(text, batch * seq, vocab)}
                    print(name, form, "temporaries", row[form]["temp_gb"],
                          "GB", flush=True)
                    for fz in row[form]["fusions"]:
                        print("   ", json.dumps(fz), flush=True)
                    continue
                scope, exe = fluid.Scope(), fluid.Executor()
                exe.run(startup, scope=scope)
                grads = [p.name + "@GRAD" for p in main.all_parameters()]
                got = exe.run(main, feed=feeds(3), scope=scope,
                              fetch_list=[loss, *grads])
                want = want or got
                assert all(np.abs(np.asarray(w)).max() > 0 for w in want)
                errs = [worst(a, b) for a, b in zip(got, want)]
                # the loss to float32 round-off; a gradient to a few of
                # its bf16 dlogits' last bits (each form rounds the
                # same float32 expression, summed in another order)
                assert errs[0] < 1e-5 and max(errs[1:]) < 2e-2, (
                    name, form, errs)
                ms, top = timed(exe, main, feeds(4), loss, scope)
                exe.close()
                row[form] = {"ms": ms, "top_ops_ms": top,
                             "worst_rel_err": [round(e, 7) for e in errs]}
            except Exception as e:      # say so, go on with the next
                row[form] = f"{type(e).__name__}: {str(e)[:300]}"
            print(name, form, json.dumps(row[form]), flush=True)
        table.append(row)
        out = (os.path.join(args.dump, "fusions.json") if args.dump else OUT)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
