"""Time the lightning indexer's selection alone on the chip, the op
``dsa_select`` (ops/dsa_ops.py) in the forms of its top-k, at
keye-train-s16384's call (b1 t16384, 16 index heads of 64 over one key
head, chunks of 512 queries by 512 keys, topk 2048), on the index
queries, keys and weights of the cell's START STATE: the eval clone of
the cell's program on its correctness sample, weights drawn from
``--seed`` as a run draws them, every layer's ``dsa_select`` operands
fetched.

    chiprun -- python benchmarks/dsa_topk_candidates.py [--parent .parent]

Forms:

- ``change``: as the module has it: the chunks whose queries have at
  most topk keys without a pass, the others' thresholds by the kernel
  ``dsa.topk.fwd`` over each chunk's causal prefix, the position passes
  under ``lax.cond``;
- ``xla``: the same with the thresholds as XLA's ops over the row's
  width (``dsa_score.topk_tile`` refusing the call: what a mesh or a CPU
  runs);
- ``parent`` (``--parent DIR``, a checkout of the commit before: ``git
  archive``): the parent's ``ops/dsa_ops.py``;
- ``NAME`` (``--form NAME=FILE``): any other copy of the module.

Every form is first held to the first one listed (the parent, where
given) on the first layer's operands: ``Selected`` and ``Live`` equal to
the bit, ``IndexLse`` equal to the bit or the largest difference; and
the selection to ``lax.top_k`` over the same scores' sortable keys
(``dsa_ops.choose_by_sort``), position for position (``top_k_agrees``:
the share of the layer's rows whose selection is top_k's own).

Then, a form: ms a call twice over [the median of five stretches of 4
calls dispatched back to back on the host's clock, the chip's busy time
over 4 traced calls (the union of the trace's ``XLA Ops`` events:
perf/trace.py)], of it the ``dsa.score.fwd`` and ``dsa.topk.fwd``
calls' own time, ms a chunk (busy / 32), the counting passes a call and
the columns they read (``pass_columns``: passes times columns, summed),
from the form's ``columns`` and the start state's ties (a module without
``columns`` walks every chunk at the row's width with 47 passes: the
parent's). ``tied`` times ``change`` on the first layer's operands with
every weight 0: every score equal, every chunk takes the position
passes.

**The share of chunks with a surplus tie** (``surplus_tie_share``): of
the chunks that make a top-k at all (28 of 32 a layer), those in which
some row has more keys equal to its threshold than it still needs, which
is where ``choose`` runs its second bisection; counted a layer through
``lax.top_k`` on the sortable keys, not through ``choose``.

The table goes to chiprun_out/dsa_topk_candidates.json. Needs a TPU;
``--tiny`` rehearses every step on the CPU at the family's tiny sizes
(times of a CPU mean nothing and are not printed as ms).
"""

import argparse
import importlib.util
import json
import os
import re
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "dsa_topk_candidates.json")
CELL = "keye-train-s16384"
PARENT_PASSES = 47      # 32 value bits, count(above), 14 position bits


def load_form(name, path):
    """A copy of ops/dsa_ops.py as a module of its own (its ops are the
    tree's already: the copy registers none)."""
    from paddle_tpu.core import registry

    spec = importlib.util.spec_from_file_location(
        "dsa_ops_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    keep = registry.register_op
    registry.register_op = lambda *a, **kw: (lambda fn: fn)
    try:
        spec.loader.exec_module(mod)
    finally:
        registry.register_op = keep
    return mod


def start_state(seed, tiny):
    """-> (attrs of the cell's ``dsa_select`` ops, [(QI, KI, W) a layer])
    at the state a run of the cell starts from."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from perf import harness, models
    from perf.kinds import train

    cell = harness.load_json("perf", "workloads", f"{CELL}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    if tiny:
        cfg.update(models.family(cfg).TINY)
        cell["traffic"] = dict(cell["traffic"], seq_len=16, real_len=[16, 16])
    fam = models.family(cfg)
    _, startup, evalp, _, _ = models.build_train(cfg, seed)
    ops = [op for op in evalp.global_block().ops if op.type == "dsa_select"]
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
    names = [op.input(slot)[0] for op in ops for slot in ("QI", "KI", "W")]
    got = [jnp.asarray(x) for x in exe.run(evalp, feed=sample,
                                           fetch_list=names, scope=scope)]
    exe.close()
    return ops[0].compute_attrs(), [tuple(got[i:i + 3])
                                    for i in range(0, len(got), 3)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--form", action="append", default=[],
                    metavar="NAME=FILE")
    ap.add_argument("--seed", type=int, default=2147487919)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import jax_cache
    from paddle_tpu.ops import dsa_ops
    from paddle_tpu.parallel import dsa_score
    from perf import trace

    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.tiny):
        print("dsa_topk_candidates: no TPU", file=sys.stderr)
        return 2
    jax_cache.configure()

    attrs, layers = start_state(args.seed, args.tiny)
    qi, ki, w = layers[0]
    b, hi, t, di = qi.shape
    scale, topk, cq, ck = dsa_ops._select_attrs(attrs, t)
    nq = t // cq
    print(f"start state of seed {args.seed}: {len(layers)} layers, QI "
          f"{qi.shape} {qi.dtype}, W {w.dtype}, topk {topk}, chunks "
          f"{cq} x {ck}", flush=True)

    files = dict(x.split("=", 1) for x in args.form)
    forms = {}
    if args.parent:
        forms["parent"] = load_form("parent", os.path.join(
            args.parent, "paddle_tpu/ops/dsa_ops.py"))
    forms["change"] = forms["xla"] = dsa_ops
    forms.update({name: load_form(name, path)
                  for name, path in files.items()})
    thresholds = dsa_score.topk_tile(cq, ck, t)

    def call_of(name):
        def select(qi_, ki_, w_):
            keep = dsa_score.topk_tile
            if name == "xla":
                dsa_score.topk_tile = lambda *a, **kw: False
            try:
                out = forms[name]._dsa_select(
                    {"QI": [qi_], "KI": [ki_], "W": [w_]}, attrs)
            finally:
                dsa_score.topk_tile = keep
            return tuple(v[0] for v in out.values())

        return jax.jit(select)

    # -- the start state's ties, by a sort ----------------------------------

    kernel = dsa_score.score_tile(cq, ck, hi, di)

    @jax.jit
    def chunk_by_sort(c, qi_c, ki_, w_c):
        """(does some row of chunk c hold a surplus tie, each row's
        selection by a sort [cq, t] bool: ``dsa_ops.choose_by_sort``)."""
        if kernel:
            scores = dsa_score.score_rows(c, qi_c, ki_, w_c, scale, ck)
        else:
            scores = jnp.concatenate(
                [dsa_ops.score_tile(qi_c, ki_[k0:k0 + ck], w_c, scale)
                 for k0 in range(0, t, ck)], axis=1)
        p_at = c * cq + jnp.arange(cq, dtype=jnp.int32)[:, None]
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] <= p_at
        chosen = dsa_ops.choose_by_sort(scores, valid, topk)
        # a key left out though it equals the least one taken
        keys = jnp.where(valid, dsa_ops._sortable(scores), jnp.uint32(0))
        least = jnp.min(jnp.where(chosen, keys, jnp.uint32(0xFFFFFFFF)),
                        axis=1, keepdims=True)
        left = jnp.logical_and(valid, jnp.logical_not(chosen))
        return jnp.any(jnp.logical_and(left, keys == least)), chosen

    first_top = min(topk // cq, nq)     # the chunks before make no top-k
    surplus, by_sort = [], []
    for layer, (qi_l, ki_l, w_l) in enumerate(layers):
        qi_t = dsa_ops._tiles(qi_l[0], cq)
        w_t = dsa_ops._tiles(w_l[0].astype(jnp.float32), cq)
        rows = [chunk_by_sort(jnp.int32(c), qi_t[c], ki_l[0, 0], w_t[c])
                for c in range(nq)]
        surplus.append([bool(s) for s, _ in rows])
        if layer == 0:
            by_sort = np.concatenate([np.asarray(ch) for _, ch in rows])
        del rows
    tops = [s[first_top:] for s in surplus]
    tied_chunks = sum(map(sum, tops))
    share = tied_chunks / max(sum(map(len, tops)), 1)
    print(f"surplus ties: {tied_chunks} of {sum(map(len, tops))} chunks "
          f"that make a top-k ({100 * share:.2f}%); a layer: "
          f"{[sum(s) for s in tops]}", flush=True)

    # -- each form against the first, and against the sort ------------------

    def ms(f, *a, calls=4):
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(calls)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / calls)
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                jax.block_until_ready([f(*a) for _ in range(calls)])
            doc = trace.load(trace.find_xplane(d))
        ops = [ev for line in doc["planes"][0]["lines"]
               if line["name"] == trace.OPS_LINE for ev in line["events"]]
        busy = trace.union_ns([(s, s + dur) for _, s, dur in ops])
        row = {"ms_host": round(statistics.median(took), 4),
               "ms_busy": round(busy / calls / 1e6, 4),
               "ms_chunk": round(busy / calls / 1e6 / nq, 4)}
        for kernel in ("dsa.score.fwd", "dsa.topk.fwd"):
            row["ms_" + kernel] = round(sum(
                dur for name, _, dur in ops
                if trace.kernel_name(name) == kernel) / calls / 1e6, 4)
        return row

    def walk(name, tied):
        """The counting passes of a call whose chunks ``tied`` [nq] hold
        a surplus tie, and the columns they read."""
        mod = forms[name]
        if not hasattr(mod, "columns"):
            return {"passes": PARENT_PASSES * nq, "columns": nq * t,
                    "pass_columns": PARENT_PASSES * nq * t}
        top = range(min(topk // cq, nq), nq)
        position = (1 + max(int(t - 1).bit_length(), 1)) * sum(
            tied[c] for c in top)
        cols = mod.columns(t, topk, cq, ck,
                           thresholds and name != "xla")["walked"]
        return {"passes": 32 * len(top) + position, "columns": cols,
                "pass_columns": 32 * cols + position * t}

    table, held = {}, None
    for name in forms:
        f = call_of(name)
        out = [np.asarray(x) for x in f(qi, ki, w)]
        row = dict(walk(name, surplus[0]))
        if held is None:
            held, row["held_to"] = out, None
        else:
            row["held_to"] = next(iter(forms))
            row["selected_equal"] = bool((out[0] == held[0]).all())
            row["live_equal"] = bool((out[1] == held[1]).all())
            row["index_lse_equal_bits"] = bool(
                (out[2].view(np.uint32) == held[2].view(np.uint32)).all())
            row["index_lse_max_diff"] = float(np.abs(out[2] - held[2]).max())
        mine = np.asarray(dsa_score.unpack(jnp.asarray(out[0]), cq))[0]
        row["top_k_agrees"] = float((mine == by_sort).all(1).mean())
        if on_chip:
            row.update(ms(f, qi, ki, w))
        table[name] = row
        print(name, json.dumps(row), flush=True)
    if on_chip:
        row = dict(walk("change", [True] * nq))
        row.update(ms(call_of("change"), qi, ki, jnp.zeros_like(w)))
        table["tied"] = row
        print("tied", json.dumps(row), flush=True)

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as fh:
        json.dump({"seed": args.seed, "shape": [b, hi, t, di, topk, cq, ck],
                   "device": jax.devices()[0].device_kind,
                   "surplus_tie_share": share,
                   "surplus_tie_chunks_a_layer": [sum(s) for s in tops],
                   "forms": table}, fh, indent=1)
    bad = [n for n, r in table.items() if r.get("held_to") and not (
        r["selected_equal"] and r["live_equal"])]
    if bad:
        print(f"dsa_topk_candidates: not the first form's selection: {bad}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
