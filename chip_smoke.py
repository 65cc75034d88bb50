"""chip_smoke.py — the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process, at
the full width of Transformer-base (d_model 512, d_inner 2048, 8 heads,
6+6 layers, vocab 10000; random weights from a seed), through the entry
points a user calls:

1. kernels — each Pallas attention family compiles with the local
   libtpu and agrees, forward and backward, with the dense reference at
   a shape a ROADMAP cell sits on; and the experts' grouped matmuls
   (``layers.topk_moe`` at OLMoE's widths, 65,536 routed rows) through
   the ``moe.*`` kernels agree, forward and every gradient, with the
   same program through ``jax.lax.ragged_dot``; the ``gdn`` and ``mla``
   phases lower the cells ``qwen3next-train-s8192`` and
   ``joyai-train-s4096`` and hold their dispatch rows (the delta rule
   and grouped-query attention; latent attention at queries and keys
   of 192 over values of 128, sigmoid routers with a selection bias)
   and run the new kernels against their plain forms; the ``sconv``
   phase lowers ``lfm2moe-train-s8192`` (four gated short convolutions
   each way on the ``sconv.gated.*`` kernels) and runs them against the
   composition; the ``kda`` phase lowers ``kimilinear-train-s4096``
   (four delta-rule calls with a decay a key feature each way on the
   ``kda.rule.*`` kernels, no rotary embedding) and runs them against
   the float32 recurrence with G below -200 inside a chunk; the
   ``xing4`` phase lowers ``xing4-train-s4096`` (a mix, a read and a
   write-back of four residual streams a sublayer each way, the mixes'
   Sinkhorn iterations on the ``hc.mix.*`` kernels, yarn tables) and
   runs the kernels against XLA's ops; the ``keye`` phase lowers
   ``keye-train-s16384`` (a ``dsa_select`` a layer on ``dsa.score.fwd``
   and ``dsa.topk.fwd``, the attention under its selection in the BHTD kernels, the indexer's
   loss) and runs both against their plain forms; the
   ``loss_head`` phase compiles a Program that is only
   ``olmoe-train-s4096``'s head and holds its temporaries under the
   float32 [tokens, vocab] tensor the loss op no longer writes;
2. train   — ``T.build`` + ``Adam.minimize`` under bf16 AMP, a few
   ``Executor.run`` steps and one ``Executor.run_steps`` window at
   b=64 s=256 with dropout 0.1 (no OOM back-off: full batch or fail);
3. serve   — ``serving.serve`` with 8 slots: eight requests of different
   source lengths submitted together must each equal their solo greedy
   decode on the same engine, with zero executor compiles after
   warm-up, and — at "highest" matmul precision — their solo decode on
   a 1-slot engine (at default precision: logits within bf16 rounding);
4. dp      — where jax reports more than one chip: the same train
   program under ``CompiledProgram.with_data_parallel`` over all of
   them, loss parity with the one-chip steps.

It needs a TPU: without one it exits non-zero before doing anything, and
nothing makes it pass off-chip. A failed check raises, so the exit code
is 0 only if every phase passed. The last line of stdout is one JSON
object naming the device. Every time it prints is an observation of this
run, not a benchmark; it measures no rate.

The phases are importable functions: tests/test_chip_smoke.py drives
them at a tiny config on the CPU.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPORT_PATH = os.path.join(HERE, "chiprun_out", "chip_smoke.json")

# (expected family, batch, seq len, causal, dropout, backward too?) at
# h=8, dh=64 — one per ROADMAP cell the family serves, plus the serving
# prefill shape
KERNEL_CASES = (
    ("bthd_small", 64, 256, False, 0.1, True),    # transformer-base train
    ("bthd_kblock", 8, 1024, True, 0.0, True),    # long-context t1024
    ("bhtd", 2, 4096, True, 0.0, True),           # long-context t4096
    ("bthd_small", 1, 32, False, 0.0, False),     # serving prefill
)
# max |kernel - reference| over max |reference|: bf16 matmul inputs and a
# bf16 probability tile against an f32 "highest"-precision reference —
# five bf16 epsilons (2^-8)
KERNEL_REL_TOL = 0.02
# the chunkwise delta rule with bf16 matmul operands against the float32
# recurrence over 1024 positions: every product of a chunk is rounded
# to bf16 (2**-8) once or twice on its way into the state, and g's and
# beta's gradients sum such products over a head's 128 x 128 state
GDN_REL_TOL = 0.05
# serving, an 8-slot against a 1-slot engine at default precision: the
# greedy head's logit for the same prefix, relative — the same five bf16
# epsilons (on the v5e 3.6e-3 at most before a parting and 1.2e-3 at
# one; 3.7e-7 at "highest" precision, where no stream parts)
SERVE_LOGIT_REL_TOL = 0.02
# data-parallel loss vs the one-chip loss on the same feeds. Without
# dropout: same math, bf16 matmuls tiled and reduced in another order
# (4e-6 seen on four v5e chips with equal masks). With dropout the
# residual and FFN masks are drawn per shard under a mesh (the dropout
# op's stream is keyed by the shard's index, ops/nn_ops._draw_bits), so
# the losses agree as two draws of the masks do: 1.6e-2 seen over 128
# tokens on the CPU mesh, 2.0e-4 over this phase's 16k tokens on four
# v5e chips
DP_LOSS_REL_TOL = 1e-3
DP_DROPOUT_LOSS_REL_TOL = 5e-2


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def transformer_base(**overrides):
    from paddle_tpu.models import transformer as T

    kw = dict(src_vocab_size=10000, trg_vocab_size=10000, d_model=512,
              d_inner=2048, n_head=8, n_layer=6)
    kw.update(overrides)
    return T.TransformerConfig(**kw)


def attention_dispatch():
    """{"family pass shape[ replicated_over=axes][ [tile]]": calls} —
    which implementation every attention call lowered so far took, and
    the tile of a family that picks one by the shape: "bhtd fwd <shape>
    [hb1 bq512 bk512]", a backward row of that family also whether it is
    one call or the pair and the sub-tiles it walks its edge blocks in:
    "... form=fused edge=256x256", a forward row the layout in which its
    logsumexp leaves the kernel: "... stats=rows", a block-masked row
    its mask: "... mask=block_diffusion block=4 band=skip", a row of a
    call given q and k in two parts who read them: "... parts=own", a
    row of a call under a selection who read it: "... sel=operand"
    (pt_attention_dispatch_total)."""
    from paddle_tpu.ops import attention_ops

    return attention_ops.dispatch_counts(tiles=True, forms=True, edges=True,
                                         stats=True, masks=True, parts=True,
                                         sels=True)


# (t, window) of the decoder cells' BHTD calls, all on hb1 bq512 bk512
ATTN_GEOMETRIES = {
    "laguna w512": (8192, 512), "smallthinker w4096": (16384, 4096),
    "t4096": (4096, None), "t8192": (8192, None), "t16384": (16384, None)}


def attention_pairs(tile=(1, 512, 512)):
    """{call: the sub-tiles its backward walks its edge blocks in, the
    score pairs a head's steps compute there (``computed_fwd``: in the
    forward, on whole blocks) and those the mask lets through}
    (flash_attention.bhtd_pairs: the call's geometry alone)."""
    from paddle_tpu.parallel import flash_attention as fa

    out = {}
    for name, (t, window) in ATTN_GEOMETRIES.items():
        computed, live = fa.bhtd_pairs(t, t, tile, True, window)
        out[name] = {
            "edge": fa.edge_label(fa.bhtd_edge_tile(tile, True)),
            "computed": computed,
            "computed_fwd": fa.bhtd_pairs(t, t, tile, True, window,
                                          form=None)[0],
            "live": live, "live_share": round(live / computed, 4)}
    return out


def _one_backward_call(attn):
    """Every BHTD backward row of a lowered cell is the ONE call
    ``attn.bhtd.bwd`` (flash_attention.bhtd_bwd_form: the cells' rows
    fit the kernel's VMEM cap), none the pair bwd_dq + bwd_dkv."""
    rows = [k for k in attn if k.startswith("bhtd bwd ")]
    check(rows and all(" form=fused" in k for k in rows),
          f"expected every bhtd backward call as one fused kernel "
          f"(form=fused), none split: {attn}")


def _statistics_in_rows(attn):
    """Every BHTD forward row of a lowered cell hands the backward its
    logsumexp as [b, h, 1, t] rows (flash_attention.bhtd_stats_form),
    none as the column the chip pads to 512 bytes a position."""
    rows = [k for k in attn if k.startswith("bhtd fwd ")]
    check(rows and all(" stats=rows" in k for k in rows),
          f"expected every bhtd forward call to write its logsumexp as "
          f"rows (stats=rows), none as the column: {attn}")


def _forward_tiles(attn, h, t, **call):
    """Every BHTD forward row of a lowered cell carries the tile
    ``flash_attention.bhtd_fwd_tile`` gives its call (``h`` heads over
    ``t`` positions and what else that function reads: two query heads
    a step where they pair, over ONE fetched K / V block under a group)
    and every backward row ``bhtd_tile``'s, one head a step: a forward
    that fell back to one head a step, or a backward that left it,
    fails here and not in a trace."""
    from paddle_tpu.parallel import flash_attention as fa

    both = {k: v for k, v in call.items()
            if k in ("dh", "group", "dv", "block_diffusion")}
    for direction, by, tile in (
            ("fwd", "bhtd_fwd_tile", fa.bhtd_fwd_tile(h, t, t, **call)),
            ("bwd", "bhtd_tile", fa.bhtd_tile(h, t, t, **both))):
        rows = [k for k in attn if k.startswith(f"bhtd {direction} ")]
        check(rows and all(f" [{fa.tile_label(tile)}]" in k for k in rows),
              f"expected every bhtd {direction} call on the tile "
              f"{fa.tile_label(tile)} ({by}): {attn}")


def _forward_on_its_tile(fn, args, h, tile, what):
    """The ONE ``attn.bhtd.fwd`` call that ``fn(*args)`` lowers walks
    ``h`` heads ``tile``'s heads a step (``bhtd_fwd_tile``'s answer for
    the call): its grid (batch rows, head steps, q-blocks, k-steps) as
    the jaxpr has it, so what Mosaic is handed."""
    import jax

    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"] == "attn.bhtd.fwd":
                    grids.append(tuple(eqn.params["grid_mapping"].grid))
                continue
            for v in eqn.params.values():
                for x in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    check(len(grids) == 1 and grids[0][1] == h // tile[0],
          f"{what}: expected one attn.bhtd.fwd call that walks {h} heads "
          f"{tile[0]} a step (bhtd_fwd_tile: {tile}), its grids: {grids}")


def _dispatch_since(before, read=attention_dispatch):
    now = read()
    return {k: v - before.get(k, 0) for k, v in sorted(now.items())
            if v > before.get(k, 0)}


def _cache_misses():
    from paddle_tpu import monitor

    return int(monitor.counter("pt_executor_cache_misses_total").value())


def compile_stages():
    """{program: {"trace" | "lower" | "backend": seconds, "hit" |
    "written": executables}} so far, from jax's own compile events as
    monitor charges them (pt_compile_stage_seconds,
    pt_compile_cache_total): "(outside)" is what no executor's first
    call was around."""
    from paddle_tpu import monitor

    snap, out = monitor.snapshot(), {}
    for r in snap["pt_compile_stage_seconds"]["values"]:
        row = out.setdefault(r["labels"]["program"], {})
        row[r["labels"]["stage"]] = round(r["sum"], 3)
    for r in snap["pt_compile_cache_total"]["values"]:
        row = out.setdefault(r["labels"]["program"], {})
        row[r["labels"]["outcome"]] = int(r["value"])
    return out


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

def kernel_phase(cases=KERNEL_CASES, h=8, dh=64):
    """Lower + compile each case with the local toolchain, run it once
    forward (and backward) and compare with the dense reference under
    ``default_matmul_precision("highest")``. With dropout the reference
    is fed the kernels' own masks."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as fa

    out = []
    for family, b, t, causal, p_drop, backward in cases:
        got = fa.bthd_family(t, t, h, dh)
        check(got == family,
              f"b{b} t{t} h{h} dh{dh}: expected family {family}, the "
              f"dispatch picked {got}")
        r = np.random.RandomState(t)
        q, k, v = (jnp.asarray(r.normal(0, 1, (b, t, h, dh)), jnp.bfloat16)
                   for _ in range(3))
        w = jnp.asarray(r.normal(0, 1, (b, t, h, dh)), jnp.float32)
        # pad-only bias [b, 1, 1, t] as the model feeds it: the last
        # eighth of the keys is padding
        pad = np.zeros((b, 1, 1, t), np.float32)
        pad[..., t - t // 8:] = -1e9
        bias = jnp.asarray(pad)
        seed = jnp.asarray(1234, jnp.int32)

        def kernel_loss(q, k, v):
            o, _ = fa.flash_attention_bthd_with_lse(
                q, k, v, bias, seed if p_drop else None, None, p_drop,
                causal)
            return jnp.sum(o.astype(jnp.float32) * w), o

        masks = (jnp.swapaxes(
            fa.bthd_dropout_masks(b, t, t, h, dh, p_drop, seed), 1, 2)
            if p_drop else None)                      # [b, h, tq, tk]

        def reference_loss(q, k, v):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh) + bias
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            if masks is not None:
                p = p * masks
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            return jnp.sum(o * w), o

        def build(fn):
            if backward:
                return jax.jit(jax.value_and_grad(fn, (0, 1, 2),
                                                  has_aux=True))
            return jax.jit(fn)

        t0 = time.perf_counter()
        lowered = build(kernel_loss).lower(q, k, v)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        got_out = jax.block_until_ready(compiled(q, k, v))
        with jax.default_matmul_precision("highest"):
            want_out = jax.block_until_ready(build(reference_loss)(q, k, v))

        def flat(res):
            if backward:
                (_, o), grads = res
                return {"out": o, "dq": grads[0], "dk": grads[1],
                        "dv": grads[2]}
            return {"out": res[1]}

        errs = {}
        for name, a in flat(got_out).items():
            ref = np.asarray(flat(want_out)[name], np.float32)
            a = np.asarray(a, np.float32)
            check(np.isfinite(a).all(), f"{family} t{t}: {name} not finite")
            errs[name] = _rel(a, ref)
            check(errs[name] <= KERNEL_REL_TOL,
                  f"{family} b{b} t{t}: {name} off the reference by "
                  f"{errs[name]:.4f} of its max (tolerance "
                  f"{KERNEL_REL_TOL})")
        tile = None
        if family == "bhtd":
            # the forward's own tile (two heads a step; the pad bias's
            # block is in its count), and the call on it
            tile = fa.bhtd_fwd_tile(h, t, t, dh=dh, bias=bias.shape)
            _forward_on_its_tile(lambda *a: kernel_loss(*a)[1], (q, k, v),
                                 h, tile, f"bhtd b{b} t{t}")
        row = {"family": family, "b": b, "t": t, "causal": causal,
               # the dispatch counter's ``tile`` label of the forward
               "tile": fa.tile_label(tile),
               "p_drop": p_drop, "backward": backward,
               "compile_s": round(compile_s, 2),
               "pallas_calls": lowered.as_text().count("tpu_custom_call"),
               "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
        say(f"  kernel {row}")
        out.append(row)
    return out


def gmm_dispatch():
    """{"pass shape [tile]": calls} — the grouped matmuls lowered so far
    and the tile of each (pt_moe_gmm_dispatch_total)."""
    from paddle_tpu.parallel import grouped_matmul as gm

    return gm.gmm_dispatch_counts()


def _moe_layer_program(tokens, d, experts, top_k, d_ff, stream=None, **kw):
    """(main, startup, fetch, names) of one ``layers.topk_moe`` layer
    under bf16 AMP with its backward pass: the output against a probe
    ``p`` as the loss; fetched are the output, the experts' rows, the
    tokens' gradient and the parameters' gradients. ``stream``: the name
    of a projection put in front of the layer, so that it gets its
    tokens as a decoder block hands them over, in the bf16 stream, and
    the rows' gradient comes back in it."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.backward import append_backward

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[tokens, d], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        probe = layers.data("p", shape=[tokens, d], dtype="float32",
                            append_batch_size=False)
        h = x
        if stream:      # a projection in front: its product is bf16
            h = layers.fc(x, d, bias_attr=False, num_flatten_dims=1,
                          param_attr=fluid.ParamAttr(name=f"{stream}.w"))
        out, _, _, rows, _ = layers.topk_moe(h, experts, top_k, d_ff, **kw)
        loss = layers.reduce_sum(layers.elementwise_mul(out, probe))
        grads = append_backward(loss)
    main._amp = True
    names = ["out", "rows", "dx"] + ["d" + p.name for p, _ in grads]
    fetch = [out, rows, "x@GRAD", *(g for _, g in grads)]
    return main, startup, fetch, names


def moe_phase(tokens=8192, d=2048, d_ff=1024, experts=64, top_k=8):
    """``layers.topk_moe`` under bf16 AMP with its backward pass, once
    through the program's grouped-matmul kernels and once, the same
    weights and tokens, through ``jax.lax.ragged_dot``: the output (the
    forward products), the tokens' gradient (the rows' gradients) and
    the three weights' gradients (the matrices' gradients) must agree.
    The default is olmoe-train-s4096's layer: 65,536 rows over 64
    experts of 2048 x 1024. Returns the errors and the counter's rows,
    which name the tile of each of the nine calls."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.parallel import grouped_matmul as gm

    def build():
        return _moe_layer_program(tokens, d, experts, top_k, d_ff,
                                  name="smoke_moe")

    r = np.random.RandomState(5)
    feed = {"x": r.randn(tokens, d).astype(np.float32),
            "p": r.randn(tokens, d).astype(np.float32)}
    scope, exe = fluid.Scope(), fluid.Executor()
    before = gmm_dispatch()
    results = {}
    enabled = gm.kernels_enabled
    for path in ("kernels", "ragged_dot"):
        main, startup, fetch, names = build()
        if path == "kernels":
            exe.run(startup, scope=scope)     # both read these weights
        else:
            gm.kernels_enabled = lambda: False
        try:
            got = exe.run(main, feed=feed, fetch_list=fetch, scope=scope,
                          return_numpy=False)
        finally:
            gm.kernels_enabled = enabled
        results[path] = dict(zip(names, jax.block_until_ready(got)))
    dispatch = _dispatch_since(before, gmm_dispatch)
    exe.close()

    m = tokens * top_k
    tiled = {k: v for k, v in dispatch.items() if k.endswith("]")}
    check(sum(tiled.values()) == 9 and sum(dispatch.values()) == 18,
          f"the layer's nine grouped matmuls did not all take a tile on "
          f"the kernel path, or the ragged_dot path took one: {dispatch}")
    rows = np.asarray(results["kernels"]["rows"])
    check(int(rows.sum()) == m and (
        rows == np.asarray(results["ragged_dot"]["rows"])).all(),
        f"the two paths routed differently: {rows.tolist()}")
    errs = {}
    for name in names:
        if name == "rows":
            continue
        a, b = results["kernels"][name], results["ragged_dot"][name]
        check(bool(jnp.isfinite(a).all()), f"moe {name} not finite")
        errs[name] = _rel(a, b)
        check(errs[name] <= KERNEL_REL_TOL,
              f"moe {name}: the kernels are off ragged_dot by "
              f"{errs[name]:.4f} of its max (tolerance {KERNEL_REL_TOL})")
    row = {"rows": m, "experts": experts, "k": d, "n": d_ff,
           "fullest_over_mean": round(float(rows.max()) * experts / m, 3),
           "dispatch": dispatch,
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  moe {row}")
    return row


def moe_held_phase(tokens=8192, d=2048, d_ff=512, experts=512, top_k=10,
                   held=(0, 32), steps=5):
    """A held share's ``layers.topk_moe`` (``held`` of ``experts``: one
    chip of an expert-parallel group) under bf16 AMP with its backward
    pass through the ``moe.*`` kernels, once as the program lowers it,
    every pass a loop over the windows of live rows
    (ops/moe_ops.over_live_rows) that starts from memory nothing filled
    (no row of ``pt_moe_buffer_fills_total`` on a TPU), and once, the
    same weights and tokens,
    with ONE window of all n * k rows, so that every pass walks the
    whole buffer: output and gradients must agree, no row of the
    counter may say ``whole``, the two token-major sums (``moe_combine
    sum_pairs``, ``moe_dispatch_grad d_x``) must say ``kernel`` in both
    (parallel/pair_sum.py's ``pairs.sum.*`` takes no window), and the
    seconds of a step of each are printed beside the live share and, on
    a TPU, the ``pairs.*`` and ``moe.*`` kernels' ms a call from a trace
    of three steps. The default is qwen3next-train-s8192's layer: a
    buffer of 81,920 rows, about 5,120 of them live."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.ops import moe_ops

    def build():
        return _moe_layer_program(tokens, d, experts, top_k, d_ff,
                                  stream="smoke_stream", name="smoke_held",
                                  held=held, norm_topk_prob=True)

    r = np.random.RandomState(5)
    feed = {"x": r.randn(tokens, d).astype(np.float32),
            "p": r.randn(tokens, d).astype(np.float32)}
    scope, exe = fluid.Scope(), fluid.Executor()
    before = (gmm_dispatch(), moe_ops.rows_dispatch_counts(),
              moe_ops.buffer_fill_counts())
    results, step_ms, kernel_ms = {}, {}, {}
    window = moe_ops.live_window
    for form in ("windowed", "whole"):
        main, startup, fetch, names = build()
        if form == "windowed":
            exe.run(startup, scope=scope)     # both read these weights
        else:
            moe_ops.live_window = lambda m, live_rows: m
        try:
            took = []
            for _ in range(steps + 1):        # the first call compiles
                t0 = time.perf_counter()
                got = jax.block_until_ready(exe.run(
                    main, feed=feed, fetch_list=fetch, scope=scope,
                    return_numpy=False))
                took.append(time.perf_counter() - t0)
        finally:
            moe_ops.live_window = window
        results[form] = dict(zip(names, got))
        step_ms[form] = round(1e3 * float(np.median(took[1:])), 2)
        if form == "windowed" and jax.default_backend() == "tpu":
            kernel_ms = {k: round(s_ / 3 * 1e3, 4) for k, s_ in sorted(
                _traced_kernel_ms("moe_held_trace", lambda: exe.run(
                    main, feed=feed, fetch_list=fetch, scope=scope,
                    return_numpy=False), "")[1].items())
                if k.startswith(("pairs.", "moe."))}
            say(f"  moe_held kernels, ms a step: {kernel_ms}")
            check(sorted(k for k in kernel_ms if k.startswith("pairs.")) == [
                "pairs.sum.combine", "pairs.sum.dispatch_grad"],
                f"expected the two pairs.sum.* kernels in the trace: "
                f"{kernel_ms}")
    gmm = _dispatch_since(before[0], gmm_dispatch)
    passes = _dispatch_since(before[1], moe_ops.rows_dispatch_counts)
    fills = _dispatch_since(before[2], moe_ops.buffer_fill_counts)
    exe.close()

    m = tokens * top_k
    check(sum(v for k, v in gmm.items() if k.endswith("]")) == 18,
          f"the two layers' eighteen grouped matmuls did not all take a "
          f"tile: {gmm}")
    sums = [k for k in passes if " sum_pairs " in k or " d_x " in k]
    check(passes and all(" windowed " in k or k in sums for k in passes),
          f"a pass of the held layer walks its buffer whole: {passes}")
    check(len(sums) == 4 and all(" kernel " in k for k in sums),
          f"a token-major sum of the held layer is not the pairs.sum.* "
          f"kernel's: {sums}")
    # on a TPU every pass starts from memory nothing filled
    # (grouped_matmul.unfilled); the CPU's carries are zeros, as ever
    check(not fills or jax.default_backend() != "tpu",
          f"a pass of the held layer fills its whole buffer: {fills}")
    w = window(m, -(-m * held[1] // experts))
    check({k.rsplit(" w", 1)[1] for k in passes} == {str(w), str(m)},
          f"the passes' windows are not {w} (and {m} for the whole "
          f"form): {passes}")
    rows = np.asarray(results["windowed"]["rows"])
    live = int(rows.sum())
    check(0 < live < m and (
        rows == np.asarray(results["whole"]["rows"])).all(),
        f"the two forms routed differently, or no row is live: "
        f"{rows.tolist()}")
    errs = {}
    for name in names:
        if name == "rows":
            continue
        a, b = results["windowed"][name], results["whole"][name]
        check(bool(jnp.isfinite(a).all()), f"held moe {name} not finite")
        errs[name] = _rel(a, b)
        check(errs[name] <= KERNEL_REL_TOL,
              f"held moe {name}: the windowed form is off the whole "
              f"buffer's by {errs[name]:.4f} of its max (tolerance "
              f"{KERNEL_REL_TOL})")
    row = {"rows": m, "live": live, "live_share": round(live / m, 4),
           "held": list(held), "experts": experts, "window": w,
           "step_ms": step_ms, "kernel_ms": kernel_ms, "passes": passes,
           "fills": fills,
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  moe_held {row}")
    return row


def lower_train_step(main, loss, seq, batch=1, sharding=None, feeds=None):
    """Lower (not run) the train step of a language-model program whose
    feeds are ``input_ids`` and ``labels`` [batch, seq] (``feeds``:
    {name: (shape, dtype)} of a program with others), as Executor.run
    would (for ``sharding``'s device, where one is described): the
    dispatch counters then hold what the step lowers, and the result
    compiles."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import lowering
    from paddle_tpu.executor import Executor

    feeds = feeds or {"input_ids": ((batch, seq), "int32"),
                      "labels": ((batch, seq), "int32")}
    low = lowering.lower_block(main, 0, tuple(feeds), (loss.name,))
    block = main.global_block()

    def aval(shape, dtype):
        dtype = jnp.dtype(dtype).name
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(
            {"int64": "int32", "float64": "float32"}.get(dtype, dtype)),
            sharding=sharding)

    def of(name):
        var = block._find_var_recursive(name)
        return aval(var.shape, var.dtype)

    return Executor._jit_for(low, None).lower(
        {n: of(n) for n in low.state_in_names},
        {name: aval(*spec) for name, spec in feeds.items()},
        aval((2,), "uint32"), aval((), "uint32"))


def cell(name, **overrides):
    """(models module, config) of the cell the phase ``name`` lowers: the
    published widths with the cell's cut of depth, vocabulary and held
    experts; ``overrides`` cut the config for the CPU tests."""
    module, config, cut = {
        "gdn": ("qwen3_next", "Qwen3NextConfig", dict(
            num_hidden_layers=4, vocab_size=18992, held_experts=(0, 32))),
        "kda": ("kimi_linear", "KimiLinearConfig", dict(
            num_hidden_layers=5, vocab_size=20480, held_experts=(0, 8))),
        "xing4": ("xing4", "Xing4Config", dict(
            num_hidden_layers=5, first_k_dense_replace=1, vocab_size=16384,
            num_nextn_predict_layers=0, held_experts=(0, 8))),
        "mla": ("joyai_flash", "JoyaiFlashConfig", dict(
            num_hidden_layers=5, vocab_size=16160, held_experts=(0, 16))),
        "ssm": ("phi4flash", "Phi4FlashConfig", dict(
            num_hidden_layers=6, first_layer=14, model_layers=32,
            vocab_size=25008)),
        "mamba2": ("nemotron_h", "NemotronHConfig", dict(
            num_hidden_layers=9, first_layer=34, vocab_size=16384,
            held_experts=(0, 8))),
        "sconv": ("lfm2_moe", "Lfm2MoeConfig", dict(
            num_hidden_layers=5, first_layer=1, vocab_size=8192,
            held_experts=(0, 8))),
        "bd": ("sdar", "SdarConfig", dict(
            num_hidden_layers=5, vocab_size=18992, mask_token_id=18991,
            held_experts=(0, 16))),
        "keye": ("keye", "KeyeConfig", dict(
            num_hidden_layers=4, vocab_size=18992, held_experts=(0, 16))),
    }[name]
    M = importlib.import_module(f"paddle_tpu.models.{module}")
    if name == "xing4":
        cut["rope_scaling"] = M.YARN
    return M, getattr(M, config)(**{**cut, **overrides})


def lower_cell(name, seq, overrides, feeds=None, **reads):
    """The train step of ``cell(name, **overrides)`` (bf16 AMP, Adam)
    LOWERED, not run (perf/run.py runs it) -> (cfg, main, {row: the rows
    ``reads[row]()``'s dispatch counter gained}): what the phase's
    ``*_rows_hold`` holds to what the cell must lower."""
    import paddle_tpu as fluid

    M, cfg = cell(name, **overrides)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        fluid.optimizer.Adam(1e-4).minimize(model["loss"])
    main._amp = True
    before = {row: read() for row, read in reads.items()}
    lower_train_step(main, model["loss"], seq, feeds=feeds)
    rows = {row: _dispatch_since(before[row], read)
            for row, read in reads.items()}
    say("  lowered: " + "; ".join(f"{k} {v}" for k, v in rows.items()))
    return cfg, main, rows


def _rel(a, b, floor=1e-6):
    """max |a - b| over max |b| (at least ``floor``), in float32."""
    import jax.numpy as jnp

    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(), floor))


def _traced_kernel_ms(name, run, prefix, calls=3):
    """{kernel: ms a call} of the Mosaic kernels whose name starts with
    ``prefix``, from a short trace of ``calls`` calls of ``run()``
    (chiprun_out/<name>), and every kernel's seconds as the trace's
    reduction has them."""
    import jax

    from perf import trace as perf_trace

    trace_dir = os.path.join(os.path.dirname(REPORT_PATH), name)
    jax.profiler.start_trace(trace_dir)
    for _ in range(calls):
        jax.block_until_ready(run())
    jax.profiler.stop_trace()
    summary = perf_trace.reduce(perf_trace.load(
        perf_trace.find_xplane(trace_dir))) or {}
    by_kernel = summary.get("by_kernel_s", {})
    return {k: round(s_ / calls * 1e3, 4) for k, s_ in by_kernel.items()
            if k.startswith(prefix)}, by_kernel


def _bhtd_against_dense(q, k, v, g, errs, what):
    """The BHTD kernels, forward and the three gradients, against the
    dense composition (causal, scale 1 / sqrt of q's width): each
    result's largest difference over the composition's largest into
    ``errs`` under ``attn_o`` .. ``attn_dv``, held to KERNEL_REL_TOL.
    -> on a TPU, where the call's backward is the one fused kernel, the
    backward kernels' ms a call by name from a short trace:
    ``attn.bhtd.bwd`` beside the pair it replaces."""
    import jax

    from paddle_tpu.parallel import flash_attention as fa

    @jax.jit
    def kernels(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=True)
        return (out, *fa.flash_attention_bwd(q, k, v, None, None, out, lse,
                                             g, causal=True))

    @jax.jit
    def dense(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
            q, k, v, None, q.shape[-1] ** -0.5, causal=True).astype(q.dtype),
            q, k, v)
        return (out, *vjp(g))

    for name, a, b in zip(("attn_o", "attn_dq", "attn_dk", "attn_dv"),
                          kernels(q, k, v, g), dense(q, k, v, g)):
        check(a.shape == b.shape, f"{what} {name}: {a.shape} != {b.shape}")
        errs[name] = _rel(a, b)
        check(errs[name] <= KERNEL_REL_TOL,
              f"{what} {name} off the dense composition by "
              f"{errs[name]:.4f} of its max (tolerance {KERNEL_REL_TOL})")
    h, hk = q.shape[1], k.shape[1]
    _forward_on_its_tile(
        lambda *a: kernels.__wrapped__(*a)[0], (q, k, v, g), h,
        fa.bhtd_fwd_tile(h, q.shape[2], k.shape[2], dh=q.shape[3],
                         group=h // hk, dv=v.shape[3],
                         itemsize=q.dtype.itemsize), what)
    if jax.default_backend() != "tpu" or fa.bhtd_bwd_form(
            h, q.shape[2], k.shape[2], dh=q.shape[3], group=h // hk,
            dv=v.shape[3], itemsize=q.dtype.itemsize) != "fused":
        return {}       # (no trace off the chip; the pair has no other form)
    # the backward kernels' time by name: the one call beside the pair
    # (the same call with no room for a resident row: bhtd_bwd_form
    # reads the cap while a call is traced)
    def as_the_pair(q, k, v, g):    # (a function jax has not traced)
        return kernels.__wrapped__(q, k, v, g)

    cap = fa._BWD_VMEM_CAP_BYTES
    fa._BWD_VMEM_CAP_BYTES = 0
    try:
        pair = jax.jit(as_the_pair).lower(q, k, v, g).compile()
    finally:
        fa._BWD_VMEM_CAP_BYTES = cap
    jax.block_until_ready(pair(q, k, v, g))
    kernel_ms, seen = _traced_kernel_ms(
        "attn_trace", lambda: (kernels(q, k, v, g), pair(q, k, v, g)),
        "attn.bhtd.bwd")
    say(f"  {what}: backward kernels, ms a call at {tuple(q.shape)}: "
        f"{kernel_ms}")
    check(sorted(kernel_ms) == ["attn.bhtd.bwd", "attn.bhtd.bwd_dkv",
                                "attn.bhtd.bwd_dq"],
          f"expected the one backward call and the pair in the trace: "
          f"{seen}")
    return kernel_ms


def gdn_dispatch():
    """{"impl pass shape chunk<C>": calls}: the gated delta-rule calls
    lowered so far (pt_linear_attention_dispatch_total)."""
    from paddle_tpu.ops import linear_attention_ops as la

    return la.dispatch_counts()


def conv_dispatch():
    """{"impl pass shape taps<n>": calls}: the causal convolutions
    lowered so far (pt_causal_conv_dispatch_total)."""
    from paddle_tpu.ops import linear_attention_ops as la

    return la.conv_dispatch_counts()


def gdn_rows_hold(cfg, seq, lowered):
    """What ``qwen3next-train-s8192``'s step must lower (gdn_phase, 1.)."""
    attn, gmm, gdn, conv = (lowered[k] for k in (
        "attention", "grouped_matmuls", "gdn", "conv"))
    n_gdn = sum(not cfg.is_full_attention(i)
                for i in range(cfg.num_hidden_layers))
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in gdn.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_gdn and all(
            k.split()[0] == "kernel" and k.endswith(f"chunk{cfg.gdn_chunk}")
            for k in rows),
            f"expected {n_gdn} delta-rule calls {direction} through the "
            f"gdn.* kernels at chunk {cfg.gdn_chunk}, none chunked, none "
            f"recurrent: {gdn}")
        rows = {k: v for k, v in conv.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_gdn and all(
            k.split()[0] == "kernel" for k in rows),
            f"expected {n_gdn} causal convolutions {direction} through the "
            f"gdn.conv.* kernels, none as XLA ops: {conv}")
    kv = f"kv{cfg.num_key_value_heads} dh{cfg.head_dim}"
    check(len(attn) == 2 and all(
        k.startswith("bhtd ") and kv in k and " [hb" in k for k in attn),
        f"expected one bhtd attention call each way at {kv} with its tile: "
        f"{attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    n_layers = cfg.num_hidden_layers
    check(sum(gmm.values()) == 9 * n_layers and all(
        "[tm128 " in k for k in gmm),
        f"expected {9 * n_layers} grouped matmuls on a tile of 128 rows "
        f"(160 rows an expert), none through ragged_dot: {gmm}")


def gdn_phase(seq=8192, t_check=1024, heads=(2, 4), width=128, gqa=(8, 2, 256),
              conv_c=1024, **overrides):
    """The hybrid decoder's new mechanisms (models/qwen3_next.py).

    1. The cell ``qwen3next-train-s8192``'s train step (one period of
       Qwen3-Next-80B-A3B at its published widths, 32 of 512 experts
       held, bf16 AMP, Adam) is LOWERED, not run (perf/run.py runs it),
       and the dispatch counters are held to what the cell must lower:
       three delta-rule calls forward and three backward, all ``kernel``
       at the configuration's chunk, and as many causal convolutions in
       front of them, all ``kernel`` too; one attention call each way at 2
       key/value heads of 256 with its tile; every grouped matmul of
       the held experts on a tile chosen for 160 rows an expert (tm128),
       none through ``ragged_dot``. ``overrides`` cut the config for the CPU tests.
    2. On the device: the chunkwise delta rule with bf16 operands (the
       ``gdn.rule.*`` kernels, whose time by name a short trace gives),
       forward and its own backward, against the step-by-step
       recurrence in float32; the causal convolution's ``gdn.conv.*``
       kernels at ``conv_c`` channels against the XLA form they replace
       (Y, dX, dW); and grouped-query attention through the BHTD kernels
       against the dense composition that copies K and V."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention_ops as la
    from paddle_tpu.parallel import causal_conv
    from paddle_tpu.parallel import flash_attention as fa

    cfg, _, rows = lower_cell(
        "gdn", seq, overrides, attention=attention_dispatch,
        grouped_matmuls=gmm_dispatch, gdn=gdn_dispatch, conv=conv_dispatch)
    gdn_rows_hold(cfg, seq, rows)

    # --- on the device ----------------------------------------------------
    r = np.random.RandomState(3)
    hk, hv = heads
    f32, bf = jnp.float32, jnp.bfloat16
    q, k = (jnp.asarray(r.randn(1, t_check, hk, width), f32) for _ in "qk")
    v, do = (jnp.asarray(r.randn(1, t_check, hv, width), f32) for _ in "vd")
    g = -jnp.asarray(r.rand(1, t_check, hv) * 0.5, f32)
    beta = jnp.asarray(r.rand(1, t_check, hv), f32)

    @jax.jit
    def chunked(q, k, v, g, beta, do):
        ins = {"Q": [q.astype(bf)], "K": [k.astype(bf)],
               "V": [v.astype(bf)], "G": [g], "Beta": [beta]}
        out = la._gated_delta_rule(ins, {"chunk": cfg.gdn_chunk})
        grads = la._gated_delta_rule_grad(
            {**ins, "States": out["States"], "GRAD::Out": [do.astype(bf)]},
            {"chunk": cfg.gdn_chunk})
        return (out["Out"][0], *(grads[f"GRAD::{s}"][0]
                                 for s in ("Q", "K", "V", "G", "Beta")))

    @jax.jit
    def recurrent(q, k, v, g, beta, do):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(la.recurrent_gated_delta_rule, q, k, v, g,
                               beta)
            return (out, *vjp(do))

    taps = cfg.linear_conv_kernel_dim
    xc, dyc = (jnp.asarray(r.randn(1, t_check, conv_c), bf) for _ in "xd")
    wc = jnp.asarray(r.randn(conv_c, taps) * 0.5, f32)
    conv_tile = causal_conv.conv_tile(t_check, conv_c, taps, bf)
    check(conv_tile is not None,
          f"no conv tile for t{t_check} c{conv_c} taps{taps}")

    @jax.jit
    def conv_kernels(x, w, dy):
        return (causal_conv.causal_conv_fwd(x, w, conv_tile),
                *causal_conv.causal_conv_bwd(x, w, dy, conv_tile))

    @jax.jit
    def conv_xla(x, w, dy):
        y, vjp = jax.vjp(lambda x, w: la._conv_xla(x, w, "silu"), x, w)
        return (y, *vjp(dy))

    errs = {}
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    got = jax.block_until_ready(chunked(q, k, v, g, beta, do))
    got_conv = jax.block_until_ready(conv_kernels(xc, wc, dyc))
    kernel_ms = {}
    if jax.default_backend() == "tpu":
        # the gdn.* kernels' time by name, from a trace of three calls
        kernel_ms, seen = _traced_kernel_ms(
            "gdn_trace", lambda: (chunked(q, k, v, g, beta, do),
                                  conv_kernels(xc, wc, dyc)), "gdn.")
        say(f"  gdn kernels, ms a call at t{t_check} hk{hk} hv{hv} "
            f"c{conv_c}: {kernel_ms}")
        check(sorted(kernel_ms) == ["gdn.conv.bwd", "gdn.conv.fwd",
                                    "gdn.rule.bwd", "gdn.rule.fwd"],
              f"expected the forward and the backward gdn.rule.* and "
              f"gdn.conv.* kernels in the trace: {seen}")
    for name, a, b in zip(names, got, recurrent(q, k, v, g, beta, do)):
        check(bool(jnp.isfinite(a).all()), f"delta rule {name} not finite")
        errs[name] = _rel(a, b)
        check(errs[name] <= GDN_REL_TOL,
              f"delta rule {name}: the chunkwise form (bf16 operands) is "
              f"off the float32 recurrence by {errs[name]:.4f} of its max "
              f"(tolerance {GDN_REL_TOL})")
    for name, a, b in zip(("conv_y", "conv_dx", "conv_dw"), got_conv,
                          conv_xla(xc, wc, dyc)):
        check(bool(jnp.isfinite(a).all()), f"{name} not finite")
        errs[name] = _rel(a, b)
        check(errs[name] <= KERNEL_REL_TOL,
              f"{name}: the gdn.conv.* kernels are off the XLA form by "
              f"{errs[name]:.4f} of its max (tolerance {KERNEL_REL_TOL})")
    h, hkv, dh = gqa
    tile = fa.bhtd_tile(h, t_check, t_check, dh=dh, group=h // hkv)
    check(tile is not None, f"no bhtd tile for h{h} kv{hkv} dh{dh}")
    qa = jnp.asarray(r.randn(1, h, t_check, dh) * 0.3, bf)
    ka = jnp.asarray(r.randn(1, hkv, t_check, dh) * 0.3, bf)
    va, ga = (jnp.asarray(r.randn(1, n, t_check, dh), bf) for n in (hkv, h))
    attn_ms = _bhtd_against_dense(qa, ka, va, ga, errs,
                                  "grouped-query attention")
    row = {**rows, "gdn_kernel_ms": kernel_ms,
           "attn_bwd_kernel_ms": attn_ms, "gqa_tile": fa.tile_label(tile),
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  gdn {row['rel_err']}")
    return row


def kda_dispatch():
    """{"impl gate pass shape chunk<C>": calls}: the delta-rule calls
    lowered so far with the label that says whose decay it is
    (pt_linear_attention_dispatch_total: ``gate=feature`` is Kimi Delta
    Attention's, ``gate=head`` the scalar rule's)."""
    from paddle_tpu.ops import linear_attention_ops as la

    return la._counts(la._M_DISPATCH, lambda lb: (
        f"{lb['impl']} {lb['gate']} {lb['pass']} {lb['shape']} "
        f"chunk{lb['chunk']}"))


def kda_rows_hold(cfg, seq, lowered):
    """What ``kimilinear-train-s4096``'s step must lower (kda_phase, 1.)."""
    kda, conv, attn, rope = (lowered[k] for k in (
        "kda", "conv", "attention", "rotary_embeddings"))
    n_kda = sum(cfg.is_kda(i) for i in range(cfg.num_hidden_layers))
    n_mla = cfg.num_hidden_layers - n_kda
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in kda.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_kda and all(
            k.startswith("kernel feature ")
            and k.endswith(f"chunk{cfg.kda_chunk}") for k in rows),
            f"expected {n_kda} delta-rule calls {direction} with a decay a "
            f"key feature through the kda.rule.* kernels at chunk "
            f"{cfg.kda_chunk}, none chunked, none recurrent: {kda}")
        rows = {k: v for k, v in conv.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_kda and all(
            k.split()[0] == "kernel" for k in rows),
            f"expected {n_kda} causal convolutions {direction} through the "
            f"gdn.conv.* kernels, none as XLA ops: {conv}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        dk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        check(sum(rows.values()) == n_mla and all(
            k.startswith("bhtd ") and f" dk{dk} dv{cfg.v_head_dim} [" in k
            for k in rows),
            f"expected {n_mla} bhtd attention calls {direction} at dk{dk} "
            f"dv{cfg.v_head_dim} with their tile, none dense: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    check(not rope, f"the model rotates nothing (mla_use_nope): {rope}")


def kda_phase(seq=4096, t_check=512, heads=2, **overrides):
    """The Kimi Linear decoder's new mechanisms (models/kimi_linear.py).

    1. The cell ``kimilinear-train-s4096``'s train step (published
       layers 1-5 at their published widths, 8 of 256 experts held,
       bf16 AMP, Adam) is LOWERED, not run, and the dispatch counters
       are held to what the cell must lower: every delta-rule call with
       a decay a key feature through the ``kda.rule.*`` kernels
       (``impl=kernel gate=feature``), none chunked, none recurrent; one
       causal convolution a KDA layer each way on the ``gdn.conv.*``
       kernels; the latent layer's one attention call each way through
       the BHTD kernels at queries and keys of 192 over values of 128,
       the backward one call; NO rotary embedding lowered at all.
       ``overrides`` cut the config for the CPU tests.
    2. On the device: ``kda.rule.fwd`` / ``kda.rule.bwd`` at ``heads``
       heads of 128 x ``t_check`` positions against the float32
       recurrence, Out and all five gradients, with gates that take G
       below -200 inside a chunk and with mild ones (the state crosses
       the chunks), and their ms a call from a trace."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention_ops as la

    cfg, _, rows = lower_cell(
        "kda", seq, overrides, kda=kda_dispatch, conv=conv_dispatch,
        attention=attention_dispatch, rotary_embeddings=rope_dispatch)
    kda_rows_hold(cfg, seq, rows)

    # --- on the device ----------------------------------------------------
    f32, bf = jnp.float32, jnp.bfloat16
    chunk = cfg.kda_chunk

    def draw(dt):
        r = np.random.RandomState(7)
        q, k, v, do = (jnp.asarray(r.randn(1, t_check, heads, 128), f32)
                       for _ in "qkvd")
        x = r.randn(1, t_check, heads, 128) * 0.3 + np.log(np.expm1(dt))
        return (q, k, v, -16.0 * jax.nn.softplus(jnp.asarray(x, f32)),
                jax.nn.sigmoid(jnp.asarray(r.randn(1, t_check, heads), f32)),
                do)

    @jax.jit
    def kernels(q, k, v, g, beta, do):
        ins = {"Q": [q.astype(bf)], "K": [k.astype(bf)],
               "V": [v.astype(bf)], "G": [g], "Beta": [beta]}
        out = la._gated_delta_rule(ins, {"chunk": chunk})
        grads = la._gated_delta_rule_grad(
            {**ins, "States": out["States"], "GRAD::Out": [do.astype(bf)]},
            {"chunk": chunk})
        return (out["Out"][0], *(grads[f"GRAD::{s}"][0]
                                 for s in ("Q", "K", "V", "G", "Beta")))

    @jax.jit
    def recurrent(q, k, v, g, beta, do):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                la.recurrent_gated_delta_rule, *(
                    x.astype(bf).astype(f32) for x in (q, k, v)), g, beta)
            return (out, *vjp(do.astype(bf).astype(f32)))

    errs, kernel_ms = {}, {}
    for gates, dt in (("steep", 0.5), ("mild", 0.002)):
        args = draw(dt)
        lowest = float(jnp.min(jnp.cumsum(args[3][:, :chunk], 1)))
        check((lowest < -200) == (gates == "steep"),
              f"{gates} gates: G reaches {lowest:.1f} inside a chunk")
        got = jax.block_until_ready(kernels(*args))
        for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got,
                              recurrent(*args)):
            check(bool(jnp.isfinite(a).all()),
                  f"kda rule {name} not finite with {gates} gates")
            key = f"{gates}.{name}"
            errs[key] = _rel(a, b, 1e-30)
            check(errs[key] <= GDN_REL_TOL,
                  f"kda rule {name}, {gates} gates: the kernels (bf16 "
                  f"operands) are off the float32 recurrence by "
                  f"{errs[key]:.4f} of its max (tolerance {GDN_REL_TOL})")
    if jax.default_backend() == "tpu":
        kernel_ms, seen = _traced_kernel_ms(
            "kda_trace", lambda: kernels(*args), "kda.")
        say(f"  kda kernels, ms a call at t{t_check} h{heads}: {kernel_ms}")
        check(sorted(kernel_ms) == ["kda.rule.bwd", "kda.rule.fwd"],
              f"expected the forward and the backward kda.rule.* kernels "
              f"in the trace: {seen}")
    row = {**rows, "kda_kernel_ms": kernel_ms,
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  kda {row['rel_err']}")
    return row


def hc_dispatch():
    """{"impl op pass": calls}: the hyper-connection calls lowered so
    far (pt_hc_dispatch_total)."""
    from paddle_tpu.ops import hc_ops

    return hc_ops.dispatch_counts()


HC_REL_TOL = 2e-3   # float32 kernels against float32 XLA ops


def xing4_rows_hold(cfg, seq, lowered):
    """What ``xing4-train-s4096``'s step must lower (xing4_phase, 1.)."""
    from paddle_tpu.parallel import hc_mix

    hc, attn, rope, routers = (lowered[k] for k in (
        "hc", "attention", "rotary_embeddings", "routers"))
    layers_, subs = cfg.num_hidden_layers, 2 * cfg.num_hidden_layers
    tile = hc_mix.mix_tile(cfg.hc_mult, seq)
    for direction in ("fwd", "bwd"):
        for op in ("mix", "pre", "post"):
            impl = "kernel" if op == "mix" and tile else "xla"
            check(hc.get(f"{impl} {op} {direction}") == subs
                  and sum(v for k, v in hc.items() if k.endswith(
                      f" {op} {direction}")) == subs,
                  f"expected {subs} hc_{op} calls {direction} as {impl}: "
                  f"{hc}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        dk = cfg.qk_head_dim
        check(sum(rows.values()) == layers_ and all(
            k.startswith("bhtd ") and f" dk{dk} dv{cfg.v_head_dim} [" in k
            for k in rows),
            f"expected {layers_} bhtd attention calls {direction} at dk{dk} "
            f"dv{cfg.v_head_dim} with their tile, none dense: {attn}")
    check(sum(rope.values()) == 2 * layers_ and all(
        k.split()[3] == f"{cfg.qk_rope_head_dim}" for k in rope),
        f"expected {layers_} rotary embeddings each way over the "
        f"{cfg.qk_rope_head_dim} shared features: {rope}")
    n_moe = layers_ - cfg.first_k_dense_replace
    check(routers and all(
        k == f"score=sigmoid bias=1 k={cfg.num_experts_per_tok} "
        f"experts={cfg.n_routed_experts}" for k in routers)
        and sum(routers.values()) >= n_moe,
        f"expected {n_moe} sigmoid routers with a selection bias: {routers}")
    if tile:
        _one_backward_call(attn)
        _statistics_in_rows(attn)


def xing4_phase(seq=4096, t_check=2048, **overrides):
    """The hyper-connected decoder's new mechanisms (models/xing4.py).

    1. The cell ``xing4-train-s4096``'s train step (a dense layer and
       four expert layers at the published widths, 8 of 64 experts held,
       no MTP module, bf16 AMP, Adam) is LOWERED, not run, and the
       dispatch counters are held to what the cell must lower: a mix, a
       read and a write-back a sublayer each way (two sublayers a
       layer), every mix's Sinkhorn iterations through the ``hc.mix.*``
       kernels; one attention call a layer each way through the BHTD
       kernels at queries and keys of 192 over values of 128, the
       backward one call; every rotary embedding with yarn's table;
       every router in its sigmoid form with a selection bias over 64
       experts, 4 a token. ``overrides`` cut the config for the CPU
       tests.
    2. On the device: ``hc.mix.fwd`` / ``hc.mix.bwd`` at 20 iterations
       over ``t_check`` tokens against the same iterations as XLA's ops,
       H_res and dZ, with logits beyond the clamp among them; H_res's
       row and column sums; and the kernels' ms a call from a trace."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import hc_ops
    from paddle_tpu.parallel import hc_mix

    cfg, _, rows = lower_cell(
        "xing4", seq, overrides, hc=hc_dispatch, attention=attention_dispatch,
        rotary_embeddings=rope_dispatch, routers=router_dispatch)
    xing4_rows_hold(cfg, seq, rows)

    # --- on the device ----------------------------------------------------
    n, iters = cfg.hc_mult, cfg.hc_sinkhorn_iters
    attrs = {"n": n, "epsilon": cfg.rms_norm_eps, "iters": iters,
             "hc_eps": cfg.hc_eps, "clamp_min": cfg.mhc_h_res_clamp_min,
             "clamp_max": cfg.mhc_h_res_clamp_max}
    r = np.random.RandomState(5)
    z = 2.0 * r.randn(n * n, t_check)
    z[1, :64], z[n + 1, 64:128] = 40.0, -40.0          # beyond the clamp
    z = jnp.asarray(z, jnp.float32)
    d = jnp.asarray(r.randn(n, n, t_check), jnp.float32)

    def both(z_, d_):
        return hc_ops._res(z_, n, attrs), hc_ops._res_grad(z_, d_, n, attrs)

    row = {**rows, "rel_err": {}, "hc_kernel_ms": {}}
    got = jax.block_until_ready(jax.jit(both)(z, d))
    res = np.asarray(got[0])
    row["rows_off_one"] = float(np.abs(res.sum(1) - 1.0).max())
    row["columns_off_one"] = float(np.abs(res.sum(0) - 1.0).max())
    check(np.isfinite(res).all() and row["columns_off_one"] < 1e-4,
          f"H_res's columns sum to 1 after the last half-step: off by "
          f"{row['columns_off_one']}")
    if hc_mix.mix_tile(n, t_check):
        keep, hc_mix.mix_tile = hc_mix.mix_tile, lambda *a: None
        try:
            want = jax.jit(both)(z, d)
        finally:
            hc_mix.mix_tile = keep
        for name, a, b in zip(("h_res", "dz"), got, want):
            row["rel_err"][name] = _rel(a, b, 1e-30)
            check(row["rel_err"][name] <= HC_REL_TOL,
                  f"hc.mix {name}: the kernel is off XLA's ops by "
                  f"{row['rel_err'][name]:.5f} of their max (tolerance "
                  f"{HC_REL_TOL})")
        if jax.default_backend() == "tpu":
            run = jax.jit(both)
            row["hc_kernel_ms"], seen = _traced_kernel_ms(
                "hc_trace", lambda: run(z, d), "hc.")
            say(f"  hc kernels, ms a call at t{t_check}: "
                f"{row['hc_kernel_ms']}")
            check(sorted(row["hc_kernel_ms"]) == ["hc.mix.bwd",
                                                  "hc.mix.fwd"],
                  f"expected the forward and the backward hc.mix.* "
                  f"kernels in the trace: {seen}")
    say(f"  hc {row['rel_err']}, rows off one {row['rows_off_one']:.2e}")
    return row


def router_dispatch():
    """{"score=.. bias=.. k=.. experts=..": calls}: the moe_router calls
    lowered so far (pt_moe_router_dispatch_total)."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get("pt_moe_router_dispatch_total", {})
    out = {}
    for r in rows.get("values", []):
        key = " ".join(f"{k}={r['labels'].get(k, '?')}"
                       for k in ("score", "bias", "k", "experts"))
        out[key] = out.get(key, 0) + int(r["value"])
    return out


def mla_rows_hold(cfg, seq, lowered):
    """What ``joyai-train-s4096``'s step must lower (mla_phase, 1.)."""
    attn, gmm, routers = (lowered[k] for k in (
        "attention", "grouped_matmuls", "routers"))
    blocks = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    n_moe = blocks - cfg.first_k_dense_replace
    dk, dv = cfg.qk_head_dim, cfg.v_head_dim
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == blocks and all(
            k.startswith("bhtd ") and f" dk{dk} dv{dv} [" in k
            for k in rows),
            f"expected {blocks} bhtd attention calls {direction} at "
            f"dk{dk} dv{dv} with their tile, none dense: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    # (the kernels read QPe and KPe's one head as operands of their own)
    check(all(k.endswith(" parts=own") for k in attn),
          f"a latent call's q and k were assembled by the op: {attn}")
    want = (f"score=sigmoid bias=1 k={cfg.num_experts_per_tok} "
            f"experts={cfg.n_routed_experts}")
    # (a router's grad op runs it again: a row counts both lowerings)
    check(set(routers) == {want} and routers[want] >= n_moe,
          f"expected {n_moe} routers lowered as {want}: {routers}")
    check(sum(gmm.values()) == 9 * n_moe and all(
        "[tm128 " in k for k in gmm),
        f"expected {9 * n_moe} grouped matmuls on a tile of 128 rows "
        f"(128 rows an expert), none through ragged_dot: {gmm}")
    # (two query heads a forward step, which share KPe's ONE head)
    _forward_tiles(attn, cfg.num_attention_heads, seq, dh=dk, dv=dv,
                   pe_group=cfg.num_attention_heads)


def mla_phase(seq=4096, t_check=1024, heads=8, **overrides):
    """The latent-attention decoder's new mechanisms
    (models/joyai_flash.py).

    1. The cell ``joyai-train-s4096``'s train step (the dense layer,
       four expert layers and the MTP module of JoyAI-LLM-Flash at its
       published widths, 16 of 256 experts held, bf16 AMP, Adam) is
       LOWERED, not run (perf/run.py runs it), and the dispatch
       counters are held to what the cell must lower: one attention
       call a block each way through the BHTD kernels at queries and
       keys of 192 over values of 128 with its tile, none dense; every
       router in its sigmoid form with a selection bias; every grouped
       matmul of the held experts on a tile chosen for 128 rows an
       expert (tm128), none through ``ragged_dot``. ``overrides`` cut
       the config for the CPU tests.
    2. On the device: the BHTD kernels at the model's two widths (8
       heads at blocks of 512, the cell's: two a forward step, one a
       backward step, so the backward is the fused call), forward and
       the three gradients, against the dense composition; and the
       forward given q and k in two parts, two heads a step over KPe's
       ONE head, against the composition of the assembled call."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as fa

    cfg, _, rows = lower_cell(
        "mla", seq, overrides, attention=attention_dispatch,
        grouped_matmuls=gmm_dispatch, routers=router_dispatch)
    mla_rows_hold(cfg, seq, rows)
    dk, dv = cfg.qk_head_dim, cfg.v_head_dim

    # --- on the device ----------------------------------------------------
    r = np.random.RandomState(5)
    bf = jnp.bfloat16
    tile = fa.bhtd_fwd_tile(heads, t_check, t_check, dh=dk, dv=dv)
    check(tile is not None, f"no bhtd tile for h{heads} dk{dk} dv{dv}")
    qa, ka = (jnp.asarray(r.randn(1, heads, t_check, dk) * 0.3, bf)
              for _ in "qk")
    va, ga = (jnp.asarray(r.randn(1, heads, t_check, dv), bf) for _ in "vg")
    errs = {}
    attn_ms = _bhtd_against_dense(qa, ka, va, ga, errs, "latent attention")
    # the forward given its queries and keys in two parts, as the cell's
    # layers give them: two heads a step read KPe's ONE head
    nope = dk - cfg.qk_rope_head_dim
    q_pe, k_pe = qa[..., nope:], ka[:, :1, :, nope:]
    own = lambda q, k, v, q_pe, k_pe: fa.flash_attention_fwd(
        q, k, v, causal=True, q_pe=q_pe, k_pe=k_pe)[0]
    parts = (qa[..., :nope], ka[..., :nope], va, q_pe, k_pe)
    check(fa.bhtd_parts(heads, t_check, t_check, dh=nope, r=dk - nope, hp=1,
                        dv=dv), f"the kernels do not take h{heads} "
          f"{nope} | {dk - nope} over {dv} in two parts at t{t_check}")
    _forward_on_its_tile(
        own, parts, heads, fa.bhtd_fwd_tile(
            heads, t_check, t_check, dh=dk, dv=dv, pe_group=heads),
        "latent attention in two parts")
    errs["parts_o"] = _rel(jax.jit(own)(*parts), jax.jit(
        lambda: fa._reference_attention(
            qa, jnp.concatenate(
                [ka[..., :nope], jnp.repeat(k_pe, heads, axis=1)], -1),
            va, None, dk ** -0.5, causal=True).astype(bf))())
    check(errs["parts_o"] <= KERNEL_REL_TOL,
          f"latent attention in two parts off the dense composition by "
          f"{errs['parts_o']:.4f} of its max (tolerance {KERNEL_REL_TOL})")
    row = {**rows, "tile": fa.tile_label(tile), "attn_bwd_kernel_ms": attn_ms,
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  mla {row['rel_err']}")
    return row


def rope_dispatch():
    """{"impl pass layout dh[ norm=head]": calls} of the rotary
    embeddings lowered so far (pt_rope_dispatch_total; ``norm=head``: a
    call that brings the heads' gains, the per-head QK-norm in the same
    pass)."""
    from paddle_tpu import monitor

    def key(labels):
        said = [labels[k] for k in ("impl", "pass", "layout", "dh")]
        if "norm" in labels:
            said.append(f"norm={labels['norm']}")
        return " ".join(said)

    # (summed: rows that differ in a label the key leaves out, as
    # ``scaling``, share a key; the last one alone hid xing4's yarn calls
    # behind lfm2's plain ones at the same head of 64, PR 68's chip run)
    out = {}
    for r in monitor.snapshot().get(
            "pt_rope_dispatch_total", {}).get("values", []):
        out[key(r["labels"])] = out.get(key(r["labels"]), 0) + int(r["value"])
    return out


def rope_phase(seq=4096, heads=(28, 4), dh=128, **overrides):
    """The rotary embedding's kernels (parallel/rope.py).

    1. A one-layer Program at SmallThinker's head shape (28 / 4 heads
       of 128, a rotating layer, bf16 AMP, Adam) runs a train step, and
       ``pt_rope_dispatch_total`` is held to one call each way through
       ``rope.fwd`` / ``rope.bwd`` from token-major q and k, none
       through XLA's ops. ``overrides`` cut the config for the CPU
       tests.
    2. On the device (Mosaic, not the interpreter): ``rope.fwd`` and
       ``rope.bwd`` at that shape, token-major in and head-major out and
       back, against ``ops/attention_ops._rotate`` behind XLA's
       transpose and its vjp, to bf16 rounding; on a TPU the two
       kernels' ms a call from a trace of three calls."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import smallthinker as M
    from paddle_tpu.ops.attention_ops import _rotate
    from paddle_tpu.parallel import rope

    h, hk = heads
    cfg = M.SmallThinkerConfig(**{**dict(
        vocab_size=512, hidden_size=256, num_hidden_layers=1,
        num_attention_heads=h, num_key_value_heads=hk, head_dim=dh,
        sliding_window_layout=(0,), rope_layout=(1,),
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=128), **overrides})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        fluid.optimizer.Adam(1e-4).minimize(model["loss"])
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    before = rope_dispatch()
    loss = float(exe.run(main, feed=M.make_batch(cfg, 1, seq, seed=3),
                         scope=scope, fetch_list=[model["loss"]])[0])
    exe.close()
    lowered = _dispatch_since(before, rope_dispatch)
    say(f"  lowered: rotary embeddings {lowered}; loss {loss:.4f}")
    check(np.isfinite(loss), f"the one-layer step's loss is {loss}")
    check(lowered == {f"kernel fwd bthd {dh}": 1, f"kernel bwd bthd {dh}": 1},
          f"expected one rope.fwd and one rope.bwd from token-major q and "
          f"k, none through XLA's ops: {lowered}")

    # --- on the device ----------------------------------------------------
    theta = cfg.rope_theta
    tile = rope.rope_tile(1, seq, h, dh, None, False, jnp.bfloat16, hk=hk)
    check(tile is not None, f"no rope tile for t{seq} h{h} kv{hk} dh{dh}")
    r = np.random.RandomState(5)
    q, k = (jnp.asarray(r.randn(1, seq, n, dh), jnp.bfloat16) for n in heads)
    gq, gk = (jnp.asarray(r.randn(1, n, seq, dh), jnp.bfloat16)
              for n in heads)

    def kernels(q, k, gq, gk):
        return (*rope.rope_fwd(q, k, theta, tile, tokens=True),
                *rope.rope_bwd(gq, gk, theta, tile, tokens=True))

    def xla(q, k, gq, gk):
        outs, vjp = jax.vjp(lambda *xs: tuple(
            _rotate(jnp.swapaxes(x, 1, 2), theta) for x in xs), q, k)
        return (*outs, *vjp((gq, gk)))

    run = jax.jit(kernels)
    got, want = run(q, k, gq, gk), jax.jit(xla)(q, k, gq, gk)
    errs = {}
    for name, a, b in zip(("q", "k", "dq", "dk"), got, want):
        a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
        check(bool(jnp.isfinite(a).all()), f"rope {name} not finite")
        # one bf16 rounding of the same float32 rotation: the last of 8
        # bits, where an FMA moved the float32 sum across a tie
        errs[name] = float(jnp.max(jnp.abs(a - b)
                                   / jnp.maximum(jnp.abs(b), 2.0 ** -6)))
        check(errs[name] <= 2.0 ** -7,
              f"rope {name}: the kernel is off _rotate by {errs[name]:.5f} "
              f"of a value (one bf16 rounding is {2.0 ** -8:.5f})")
    kernel_ms = {}
    if jax.default_backend() == "tpu":
        kernel_ms, _ = _traced_kernel_ms(
            "rope_trace", lambda: run(q, k, gq, gk), "rope.")
        check(sorted(kernel_ms) == ["rope.bwd", "rope.fwd"],
              f"expected rope.fwd and rope.bwd in the trace: {kernel_ms}")
    row = {"lowered": lowered, "tile": list(tile), "kernel_ms": kernel_ms,
           "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}
    say(f"  rope {row}")
    return row


def loss_head_program(batch, seq, width, vocab, soft=False, xent=None,
                      table_rows=1024):
    """(main, startup, loss, feeds) of a language model that is only its
    head, as the cells' models build theirs, under bf16 AMP with Adam:
    ``embed`` (a small table, so the step is fed ids as a cell's is),
    ``final_norm``, and under ``loss_head`` the projection to ``vocab``,
    ``xent`` (layers.softmax_with_cross_entropy) on hard labels, or on
    smoothed one-hot rows as models/transformer.py makes them, and the
    mean. ``feeds(seed)`` draws a step's ids and labels."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    xent = xent or layers.softmax_with_cross_entropy
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        ids = layers.data("input_ids", shape=[batch, seq], dtype="int64",
                          append_batch_size=False)
        lbl = layers.data("labels", shape=[batch, seq], dtype="int64",
                          append_batch_size=False)
        with fluid.name_scope("embed"):
            x = layers.embedding(ids, size=[table_rows, width],
                                 param_attr=fluid.ParamAttr(name="tok_emb.w"))
        with fluid.name_scope("final_norm"):
            x = layers.rms_norm(x, param_attr=fluid.ParamAttr(
                name="final_norm.scale"))
        with fluid.name_scope("loss_head"):
            logits = layers.fc(x, vocab, num_flatten_dims=2, bias_attr=False,
                               param_attr=fluid.ParamAttr(name="lm_head.w"))
            if soft:
                ce = xent(logits, layers.label_smooth(
                    layers.one_hot(lbl, vocab), epsilon=0.1), soft_label=True)
            else:
                ce = xent(logits, layers.unsqueeze(lbl, [2]))
            loss = layers.mean(ce)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    main._amp = True

    def feeds(seed):
        r = np.random.RandomState(seed)
        return {"input_ids": r.randint(0, table_rows, (batch, seq)),
                "labels": r.randint(0, vocab, (batch, seq))}

    return main, startup, loss, feeds


def loss_head_phase(batch=2, seq=4096, width=2048, vocab=50304, steps=6,
                    temp_share=1.03):
    """softmax_with_cross_entropy and its grad op at
    ``olmoe-train-s4096``'s call (8192 tokens x 2048 -> 50304, bf16
    logits) in a Program that is only the head.

    1. The step lowers one call each way, on hard labels, with no
       gradient of Softmax (``pt_loss_head_dispatch_total``).
    2. Compiled for this device, ``memory_analysis()``'s temporaries
       are under ``temp_share`` of what the float32 [tokens, vocab]
       tensor alone would take (1.65 GB there: the bf16 logits, the
       weight's gradient and the rest fit under it; the composition
       with the float32 log-probabilities read over 2.4 GB). None: not
       held (the CPU's compiler fuses otherwise).
    3. ``steps`` calls of Executor.run: the loss is finite, and the
       wall ms a call is printed (an observation)."""
    import paddle_tpu as fluid
    from paddle_tpu.ops.nn_ops import loss_head_dispatch_counts

    main, startup, loss, feeds = loss_head_program(batch, seq, width, vocab)
    before = loss_head_dispatch_counts()
    mem = lower_train_step(main, loss, seq, batch).compile().memory_analysis()
    lowered = _dispatch_since(before, loss_head_dispatch_counts)
    f32_logits = 4 * batch * seq * vocab
    say(f"  lowered: loss heads {lowered}; temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB (float32 logits alone "
        f"{f32_logits / 1e9:.3f}), peak {mem.peak_memory_in_bytes / 1e9:.3f}")
    check(lowered == {"hard fwd 0": 1, "hard bwd 0": 1},
          f"expected one hard-label call each way and no gradient of "
          f"Softmax: {lowered}")
    if temp_share is not None:
        check(mem.temp_size_in_bytes < temp_share * f32_logits,
              f"the step's temporaries ({mem.temp_size_in_bytes / 1e9:.3f} "
              f"GB) have room for a float32 [tokens, vocab] tensor "
              f"({f32_logits / 1e9:.3f} GB)")
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    batches = [feeds(s) for s in range(2)]
    losses, secs = _timed_steps(exe, main, batches, steps + 1, loss, scope)
    exe.close()
    check(bool(np.isfinite(losses).all()), f"the head's losses: {losses}")
    row = {"lowered": lowered, "shape": [batch * seq, width, vocab],
           "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
           "peak_gb": round(mem.peak_memory_in_bytes / 1e9, 3),
           "loss": [round(v, 4) for v in (losses[0], losses[-1])],
           "ms_per_call": round(float(np.median(secs[1:])) * 1e3, 3)}
    say(f"  loss_head {row}")
    return row


def ssm_dispatch():
    from paddle_tpu.ops import selective_scan_ops

    return selective_scan_ops.dispatch_counts()


def ssm_rows_hold(cfg, seq, lowered):
    """What ``phi4flash-train-s4096``'s step must lower (ssm_phase, 1.)."""
    attn, scans, convs = (lowered[k] for k in (
        "attention", "selective_scans", "convolutions"))
    e, n = cfg.mamba_d_inner, cfg.mamba_d_state
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in scans.items() if f" {direction} " in k}
        check(sum(rows.values()) == 2 and all(
            k.startswith("kernel ") and f"t{seq} e{e} n{n}" in k
            for k in rows),
            f"expected 2 selective scans {direction} on the ssm.scan "
            f"kernels: {scans}")
        rows = {k: v for k, v in convs.items() if f" {direction} " in k}
        check(sum(rows.values()) == 2 and all(
            k.startswith("kernel ") for k in rows),
            f"expected 2 convolutions {direction} on the gdn.conv "
            f"kernels: {convs}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == 6 and all(
            k.startswith("bhtd ") and f" dk{cfg.head_dim} "
            f"dv{2 * cfg.head_dim}" in k for k in rows),
            f"expected 6 bhtd attention calls {direction} at "
            f"dk{cfg.head_dim} dv{2 * cfg.head_dim}, none dense: {attn}")
        check(sum(v for k, v in rows.items()
                  if f" w{cfg.sliding_window}" in k) == 2,
              f"expected the window layer's two calls {direction} with "
              f"their window: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)


def ssm_phase(seq=4096, t_check=1024, **overrides):
    """The state-space hybrid's new mechanisms (models/phi4flash.py).

    1. The cell ``phi4flash-train-s4096``'s train step (layers 14-19 of
       Phi-4-mini-flash at its published widths, an eighth of the tied
       table, bf16 AMP, Adam) is LOWERED, not run (perf/run.py runs it),
       and the dispatch counters are held to what the cell must lower:
       its two selective scans each way on the ``ssm.scan.*`` kernels
       (``impl=kernel``), its two convolutions (with a bias) on the
       ``gdn.conv.*`` kernels, and six attention calls each way (two
       softmax maps in each of the window, the full and the cross layer)
       through the BHTD kernels at dk64 dv128, none dense, the window
       layer's with a band, every backward one call (``form=fused``).
       ``overrides`` cut the config for the CPU tests.
    2. On the device, at the cell's channels and ``t_check`` positions:
       the scan kernels against the chunked XLA writing (gated, as layer
       14's call, forward and every gradient), the convolution kernels
       with a bias against the XLA writing, and the kernels' ms a call
       by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention_ops as L
    from paddle_tpu.ops import selective_scan_ops as S
    from paddle_tpu.parallel import selective_scan as K

    cfg, _, rows = lower_cell(
        "ssm", seq, overrides, attention=attention_dispatch,
        selective_scans=ssm_dispatch, convolutions=conv_dispatch)
    ssm_rows_hold(cfg, seq, rows)
    e, n = cfg.mamba_d_inner, cfg.mamba_d_state

    # --- on the device ----------------------------------------------------
    r = np.random.RandomState(7)
    bf, f32 = jnp.bfloat16, jnp.float32
    t = t_check
    ins = {"X": jnp.asarray(r.randn(1, t, e), bf),
           "Dt": jnp.asarray(r.randn(1, t, e) - 3.0, bf),
           "A": -jnp.asarray(np.tile(np.arange(1, n + 1), (e, 1)), f32),
           "B": jnp.asarray(r.randn(1, t, n), bf),
           "C": jnp.asarray(r.randn(1, t, n), bf),
           "D": jnp.ones((e,), f32),
           "Z": jnp.asarray(r.randn(1, t, e), bf),
           "DtBias": jnp.asarray(r.randn(e) * 0.5, f32)}
    dy = jnp.asarray(r.randn(1, t, e), bf)
    check(K.ssm_tile(t, e, n, bf) is not None,
          f"no ssm tile for t{t} e{e} n{n}")

    def scan(ins, dy):
        wrapped = {k: [v] for k, v in ins.items()}
        out = S._selective_scan(wrapped, {})
        grads = S._selective_scan_grad(
            {**wrapped, "States": out["States"], "GRAD::Out": [dy]}, {})
        return {"Out": out["Out"][0], **{k: v[0] for k, v in grads.items()}}

    kernels = jax.jit(scan)
    got = jax.block_until_ready(kernels(ins, dy))
    tile, K.ssm_tile = K.ssm_tile, lambda *a, **k: None
    try:
        # (a function of its own: jax.jit(scan) again would hand back
        # the kernels' executable)
        want = jax.block_until_ready(
            jax.jit(lambda ins, dy: scan(ins, dy))(ins, dy))
    finally:
        K.ssm_tile = tile
    check(want["Out"] is not got["Out"] and any(
        bool(jnp.any(got[k] != want[k])) for k in want),
        "the XLA writing's results are the kernels' bit for bit: the "
        "comparison ran one of them twice")
    errs = {f"scan {k}": _rel(got[k], want[k]) for k in want}
    x, w = ins["X"], jnp.asarray(r.randn(e, cfg.mamba_d_conv) * 0.5, f32)
    bias = jnp.asarray(r.randn(e), f32)

    def conv(x, w, bias, dy):
        wrapped = {"X": [x], "W": [w], "Bias": [bias]}
        y = L._causal_conv1d(wrapped, {"act": "silu"})["Y"][0]
        g = L._causal_conv1d_grad({**wrapped, "GRAD::Y": [dy]},
                                  {"act": "silu"})
        return {"Y": y, **{k: v[0] for k, v in g.items()}}

    conv_kernels = jax.jit(conv)
    got = jax.block_until_ready(conv_kernels(x, w, bias, dy))
    xla = lambda x, w, b: L._conv_xla(x, w, "silu", b)
    y_ref, vjp = jax.vjp(xla, x, w, bias)
    for k, ref in zip(("Y", "GRAD::X", "GRAD::W", "GRAD::Bias"),
                      (y_ref, *vjp(dy))):
        errs[f"conv {k}"] = _rel(got[k], ref)
    # (bf16 results of float32 sums in another order: 0.002-0.004 seen
    # for the scan, 0.004-0.008 for the convolution: my chip runs, PR 40)
    check(max(errs.values()) < 2e-2,
          f"ssm kernels against the XLA writings: {errs}")
    ms, _ = _traced_kernel_ms(
        "chip_smoke_ssm", lambda: (kernels(ins, dy),
                                   conv_kernels(x, w, bias, dy)), "")
    ms = {k: v for k, v in ms.items() if k.startswith(("ssm.", "gdn.conv"))}
    # (a trace needs the chip: the CPU tests run this phase through the
    # interpreters and read {})
    check(jax.default_backend() != "tpu"
          or {"ssm.scan.fwd", "ssm.scan.bwd", "gdn.conv.fwd",
              "gdn.conv.bwd"} <= set(ms), f"kernels in the trace: {ms}")
    row = {**rows, "kernel_ms": ms,
           "rel_err": {k_: round(v, 5) for k_, v in errs.items()}}
    say(f"  ssm kernels, ms a call at t{t} e{e} n{n}: {ms}")
    say(f"  ssm {row['rel_err']}")
    return row


def mamba2_dispatch():
    from paddle_tpu.ops import mamba2_scan_ops

    return mamba2_scan_ops.dispatch_counts()


def mamba2_rows_hold(cfg, seq, lowered):
    """What ``nemotron3nano-train-s4096``'s step must lower (mamba2_phase,
    1.)."""
    attn, scans, convs, gmms = (lowered[k] for k in (
        "attention", "mamba2_scans", "convolutions", "grouped_matmuls"))
    kinds = [k for _, k in cfg.blocks]
    n_scan, n_moe, n_attn = (kinds.count(k)
                             for k in ("mamba2", "moe", "attn"))
    shape = (f"t{seq} h{cfg.mamba_num_heads} p{cfg.mamba_head_dim} "
             f"g{cfg.n_groups} n{cfg.ssm_state_size}")
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in scans.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_scan and all(
            k.startswith("kernel ") and shape in k for k in rows),
            f"expected {n_scan} mamba2 scans {direction} on the "
            f"mamba2.chunk kernels: {scans}")
        rows = {k: v for k, v in convs.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_scan and all(
            k.startswith("kernel ") for k in rows),
            f"expected {n_scan} convolutions {direction} on the gdn.conv "
            f"kernels: {convs}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_attn and all(
            k.startswith("bhtd ") and f" h{cfg.num_attention_heads} "
            f"kv{cfg.num_key_value_heads} " in k for k in rows),
            f"expected {n_attn} bhtd attention call {direction} at "
            f"{cfg.num_attention_heads} / {cfg.num_key_value_heads} heads, "
            f"none dense: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    check(sum(gmms.values()) == 6 * n_moe and all(
        k.endswith("]") for k in gmms),
        f"expected {6 * n_moe} grouped matmuls (two matrices an expert, "
        f"three products each), every one on a tile: {gmms}")


def mamba2_phase(seq=4096, t_check=1024, **overrides):
    """The Mamba-2 / attention hybrid's new mechanisms
    (models/nemotron_h.py).

    1. The cell ``nemotron3nano-train-s4096``'s train step (blocks 34-42
       of NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, 8 of
       128 experts held, an eighth of the vocabulary, bf16 AMP, Adam) is
       LOWERED, not run (perf/run.py runs it), and the dispatch counters
       are held to what the cell must lower: its four Mamba-2 scans each
       way on the ``mamba2.chunk.*`` kernels (``impl=kernel``), their
       four convolutions (with a bias) on the ``gdn.conv.*`` kernels,
       every grouped matmul of the four expert layers on a tile (none as
       ``ragged_dot``: the experts' width of 1856 is off the 128 lanes),
       and the one attention call each way through the BHTD kernels at
       32 / 2 heads, the backward one call (``form=fused``).
       ``overrides`` cut the config for the CPU tests.
    2. On the device, at the cell's heads and ``t_check`` positions: the
       scan kernels against the chunked XLA writing (forward and every
       gradient), the grouped matmuls at the experts' widths against
       ``ragged_dot``, and the kernels' ms a call by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import mamba2_scan_ops as S
    from paddle_tpu.parallel import grouped_matmul as gm
    from paddle_tpu.parallel import mamba2_scan as K

    cfg, _, rows = lower_cell(
        "mamba2", seq, overrides, attention=attention_dispatch,
        mamba2_scans=mamba2_dispatch, convolutions=conv_dispatch,
        grouped_matmuls=gmm_dispatch)
    mamba2_rows_hold(cfg, seq, rows)
    shape = (f"t{seq} h{cfg.mamba_num_heads} p{cfg.mamba_head_dim} "
             f"g{cfg.n_groups} n{cfg.ssm_state_size}")

    # --- on the device ----------------------------------------------------
    r = np.random.RandomState(7)
    bf, f32 = jnp.bfloat16, jnp.float32
    t, heads, p = t_check, cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    ins = {"X": jnp.asarray(r.randn(1, t, heads * p), bf),
           "Dt": jnp.asarray(r.randn(1, t, heads) - 3.0, bf),
           "ALog": jnp.asarray(np.log(np.arange(1, heads + 1)), f32),
           "B": jnp.asarray(r.randn(1, t, groups * n) * 0.5, bf),
           "C": jnp.asarray(r.randn(1, t, groups * n) * 0.5, bf),
           "D": jnp.ones((heads,), f32),
           "DtBias": jnp.asarray(r.randn(heads) * 0.5, f32)}
    dy = jnp.asarray(r.randn(1, t, heads * p), bf)
    attrs = {"groups": groups, "chunk": cfg.chunk_size}
    check(K.mamba2_tile(t, heads, groups, p, n, cfg.chunk_size, bf)
          is not None, f"no mamba2 tile for t{t} {shape}")

    def scan(ins, dy):
        wrapped = {k: [v] for k, v in ins.items()}
        out = S._mamba2_scan(wrapped, attrs)
        grads = S._mamba2_scan_grad(
            {**wrapped, "States": out["States"], "GRAD::Out": [dy]}, attrs)
        return {"Out": out["Out"][0], **{k: v[0] for k, v in grads.items()}}

    kernels = jax.jit(scan)
    got = jax.block_until_ready(kernels(ins, dy))
    tile, K.mamba2_tile = K.mamba2_tile, lambda *a, **k: None
    try:
        # (a function of its own: jax.jit(scan) again would hand back
        # the kernels' executable)
        want = jax.block_until_ready(
            jax.jit(lambda ins, dy: scan(ins, dy))(ins, dy))
    finally:
        K.mamba2_tile = tile
    check(want["Out"] is not got["Out"] and any(
        bool(jnp.any(got[k] != want[k])) for k in want),
        "the XLA writing's results are the kernels' bit for bit: the "
        "comparison ran one of them twice")
    errs = {f"scan {k}": _rel(got[k], want[k]) for k in want}

    # the experts' grouped matmuls at their widths and the cell's own
    # rows (a held share: an eighth of a row tile an expert at t_check
    # positions would be ragged_dot's)
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    held = cfg.held_experts[1]
    m = seq * cfg.num_experts_per_tok
    live = m * held // cfg.n_routed_experts
    check(gm.gmm_tile(m, d, f, held, bf, live_rows=live) is not None,
          f"no tile for {m} rows of {d} x {f}, {live} live")
    sizes = jnp.asarray(np.diff(np.r_[0, np.sort(r.randint(
        0, live, held - 1)), live]), jnp.int32)
    x, w = (jnp.asarray(r.randn(m, d), bf),
            jnp.asarray(r.randn(held, d, f) * 0.05, bf))
    g = jnp.asarray(r.randn(m, f), bf)

    def products(x, w, g, sizes):
        y = gm.grouped_matmul(x, w, sizes, live_rows=live)
        return (y, *gm.grouped_matmul_grads(x, w, sizes, g, live_rows=live))

    def ragged(x, w, g, sizes):
        y, vjp = jax.vjp(lambda a, b_: jax.lax.ragged_dot(a, b_, sizes),
                         x, w)
        return (y, *vjp(g))

    gmm_kernels = jax.jit(products)
    got = jax.block_until_ready(gmm_kernels(x, w, g, sizes))
    # the rows inside groups against ragged_dot's (what its transposes
    # leave behind the last group is not defined); ours: zeros behind
    want = jax.jit(ragged)(x, w, jnp.where(
        jnp.arange(m)[:, None] < live, g, 0), sizes)
    for name, a, b in zip(("gmm y", "gmm dx"), got, want):
        errs[name] = _rel(a[:live], b[:live])
        check(not bool(jnp.any(a[live:] != 0)),
              f"{name} holds something behind the last live row")
    errs["gmm dw"] = _rel(got[2], want[2])
    check(max(errs.values()) < 2e-2,
          f"mamba2 and off-lane moe kernels against the XLA writings: "
          f"{errs}")
    ms, _ = _traced_kernel_ms(
        "chip_smoke_mamba2", lambda: (kernels(ins, dy),
                                      gmm_kernels(x, w, g, sizes)), "")
    ms = {k: v for k, v in ms.items() if k.startswith(("mamba2.", "moe."))}
    # (a trace needs the chip: the CPU tests run this phase through the
    # interpreters and read {})
    check(jax.default_backend() != "tpu"
          or {"mamba2.chunk.fwd", "mamba2.chunk.bwd", "moe.gmm.fwd",
              "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"} <= set(ms),
          f"kernels in the trace: {ms}")
    row = {**rows, "kernel_ms": ms,
           "rel_err": {k_: round(v, 5) for k_, v in errs.items()}}
    say(f"  mamba2 kernels, ms a call at t{t} {shape}: {ms}")
    say(f"  mamba2 {row['rel_err']}")
    return row


def sconv_rows_hold(cfg, seq, lowered):
    """What ``lfm2moe-train-s8192``'s step must lower (sconv_phase, 1.)."""
    attn, convs, ropes = (lowered[k] for k in (
        "attention", "convolutions", "rotary_embeddings"))
    kinds = [k for _, k, _ in cfg.blocks]
    n_conv, n_attn = kinds.count("sconv"), kinds.count("attn")
    c, taps = cfg.hidden_size, cfg.conv_L_cache
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in convs.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_conv and all(
            k == f"kernel {direction} b1 t{seq} c{c} taps{taps} gated"
            for k in rows),
            f"expected {n_conv} gated convolutions {direction} on the "
            f"sconv.gated kernels: {convs}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == n_attn and all(
            k.startswith("bhtd ") and f" h{cfg.num_attention_heads} "
            f"kv{cfg.num_key_value_heads} dh{cfg.head_dim} " in k
            for k in rows),
            f"expected {n_attn} bhtd attention call {direction} at "
            f"{cfg.num_attention_heads} / {cfg.num_key_value_heads} heads "
            f"of {cfg.head_dim}, none dense: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    check(sum(ropes.values()) == 2 * n_attn,
          f"expected {n_attn} rotary embedding each way: {ropes}")


def sconv_phase(seq=8192, t_check=2048, **overrides):
    """The short-convolution / attention hybrid's new mechanisms
    (models/lfm2_moe.py).

    1. The cell ``lfm2moe-train-s8192``'s train step (layers 1-5 of
       LFM2-24B-A2B at its published widths, 8 of 64 experts held, an
       eighth of the vocabulary, bf16 AMP, Adam) is LOWERED, not run
       (perf/run.py runs it), and the dispatch counters are held to what
       the cell must lower: its four gated short convolutions each way
       on the ``sconv.gated.*`` kernels (rows of
       ``pt_causal_conv_dispatch_total`` that carry ``gated``,
       ``impl=kernel``), and the one attention call each way through the
       BHTD kernels at 32 / 8 heads of 64, the backward one call
       (``form=fused``). The rotary embedding's rows are printed, not
       held: ``rope_tile`` refuses a head of 64 (two heads a vreg) and
       the cell's one call each way is ``impl=xla`` until a kernel takes
       it. ``overrides`` cut the config for the CPU tests.
    2. On the device, at the cell's channels and ``t_check`` positions:
       the gated kernels against the composition in XLA ops (Y, dB, dC,
       du, dW), and the kernels' ms a call by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention_ops as L
    from paddle_tpu.parallel import causal_conv as K

    cfg, _, rows = lower_cell(
        "sconv", seq, overrides, attention=attention_dispatch,
        convolutions=conv_dispatch, rotary_embeddings=rope_dispatch)
    sconv_rows_hold(cfg, seq, rows)
    c, taps = cfg.hidden_size, cfg.conv_L_cache

    # --- on the device ----------------------------------------------------
    r = np.random.RandomState(7)
    bf, f32 = jnp.bfloat16, jnp.float32
    t = t_check
    x = jnp.asarray(r.randn(1, t, 3 * c), bf)
    w = jnp.asarray(r.uniform(-1, 1, (c, taps)) * taps ** -0.5, f32)
    dy = jnp.asarray(r.randn(1, t, c), bf)
    check(K.conv_tile(t, c, taps, bf, gated=True) is not None,
          f"no tile for the gated convolution at t{t} c{c} taps{taps}")

    def conv(x, w, dy):
        wrapped = {"X": [x], "W": [w]}
        y = L._gated_short_conv(wrapped, {})["Y"][0]
        g = L._gated_short_conv_grad({**wrapped, "GRAD::Y": [dy]}, {})
        dx = g["GRAD::X"][0]
        return {"Y": y, "dB": dx[..., :c], "dC": dx[..., c:2 * c],
                "du": dx[..., 2 * c:], "dW": g["GRAD::W"][0]}

    kernels = jax.jit(conv)
    got = jax.block_until_ready(kernels(x, w, dy))
    y_ref, vjp = jax.vjp(L._gated_conv_xla, x, w)
    dx_ref, dw_ref = vjp(dy)
    want = {"Y": y_ref, "dB": dx_ref[..., :c], "dC": dx_ref[..., c:2 * c],
            "du": dx_ref[..., 2 * c:], "dW": dw_ref}
    errs = {k: _rel(got[k], want[k]) for k in want}
    # (bf16 results of float32 sums in another order)
    check(max(errs.values()) < 2e-2,
          f"sconv.gated kernels against the XLA writing: {errs}")
    ms, _ = _traced_kernel_ms("chip_smoke_sconv",
                              lambda: kernels(x, w, dy), "")
    ms = {k: v for k, v in ms.items() if k.startswith("sconv.")}
    # (a trace needs the chip: the CPU tests run this phase through the
    # interpreters and read {})
    check(jax.default_backend() != "tpu"
          or {"sconv.gated.fwd", "sconv.gated.bwd"} <= set(ms),
          f"kernels in the trace: {ms}")
    row = {**rows, "kernel_ms": ms,
           "rel_err": {k_: round(v, 5) for k_, v in errs.items()}}
    say(f"  sconv kernels, ms a call at t{t} c{c} taps{taps}: {ms}")
    say(f"  sconv {row['rel_err']}")
    return row


def bd_rows_hold(cfg, seq, lowered):
    """What ``sdar-train-s4096``'s step must lower (bd_phase, 1.)."""
    attn, ropes = lowered["attention"], lowered["rotary_embeddings"]
    n, block = cfg.num_hidden_layers, cfg.block_length
    for direction in ("fwd", "bwd"):
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == n and all(
            k.startswith(f"bhtd {direction} b1 tq{2 * seq} tk{2 * seq} ")
            and k.endswith(f" mask=block_diffusion block={block} band=skip")
            for k in rows),
            f"expected {n} block-masked attention calls {direction} over "
            f"{2 * seq} positions in the bhtd kernels (band=skip), none "
            f"dense: {attn}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    check(sum(ropes.values()) == 2 * n and all(
        k.startswith("kernel ") and k.endswith(" norm=head") for k in ropes),
        f"expected {n} rotary embeddings each way on the rope kernels "
        f"with the heads' gains (norm=head), none as XLA's ops: {ropes}")
    _forward_tiles(attn, cfg.num_attention_heads, 2 * seq,
                   dh=cfg.head_dim,
                   group=cfg.num_attention_heads // cfg.num_key_value_heads,
                   block_diffusion=block)


def bd_phase(seq=4096, t_check=1024, heads=(32, 4), dh=128, **overrides):
    """Training by block diffusion (models/sdar.py): a row of [noised
    copy ; clean copy] under the three-part block mask inside the BHTD
    kernels.

    1. The cell ``sdar-train-s4096``'s train step (five layers of
       SDAR-30B-A3B at its published widths, 16 of 128 experts held, an
       eighth of the vocabulary, bf16 AMP, Adam) is LOWERED, not run
       (perf/run.py runs it), and the dispatch counters are held to what
       the cell must lower: five block-masked attention calls each way
       over 2 x ``seq`` positions on the BHTD kernels (``mask=
       block_diffusion band=skip``: the kernels walk the mask's live
       blocks; none ``dense``), the backward one call (``form=fused``),
       the logsumexp in rows, and five rotary embeddings each way on the
       ``rope.*`` kernels (the positions run twice over the row: one
       run's tables read twice), every one with the heads' gains
       (``norm=head``: the per-head QK-norm rides in the kernels' pass,
       no ``rms_norm`` op is left in a block's attention), none as
       XLA's ops. ``overrides`` cut the config for the CPU tests.
    2. On the device, at the cell's heads and 2 x ``t_check`` positions:
       the kernels under the mask, forward and the three gradients,
       against the dense composition (``flash_attention.bd_visible``),
       the score pairs the two walks compute beside those the mask lets
       through, and the kernels' ms a call by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as fa

    cfg, main, rows = lower_cell(
        "bd", seq, overrides, attention=attention_dispatch,
        rotary_embeddings=rope_dispatch, feeds={
            "input_ids": ((1, 2 * seq), "int32"),
            "labels": ((1, seq), "int32"),
            "loss_weight": ((1, seq), "float32")})
    bd_rows_hold(cfg, seq, rows)
    n, block = cfg.num_hidden_layers, cfg.block_length
    normed = [op.type for op in main.global_block().ops
              if op.type.startswith("rms_norm") and "/attn/" in
              (op.namescope or "") + "/"]
    check(len(normed) == 2 * n,
          f"expected one rms_norm (the pre-norm) and its grad op a block's "
          f"attention, the QK-norm inside the rotary op: {len(normed)} "
          f"under the {n} blocks' attn scopes")

    # --- on the device ----------------------------------------------------
    (h, hk), t = heads, 2 * t_check
    tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk, block_diffusion=block)
    check(tile is not None, f"no tile for the block-masked call at t{t} "
          f"h{h} kv{hk} dh{dh}")
    r = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(r.randn(1, n_heads, t, dh), jnp.bfloat16)
                  for n_heads in (h, hk, hk, h))

    @jax.jit
    def kernels(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, block_diffusion=block)
        return (out, *fa.flash_attention_bwd(q, k, v, None, None, out, lse,
                                             g, block_diffusion=block))

    @jax.jit
    def dense(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
            q, k, v, None, dh ** -0.5,
            block_diffusion=block).astype(q.dtype), q, k, v)
        return (out, *vjp(g))

    _forward_on_its_tile(
        lambda *a: kernels.__wrapped__(*a)[0], (q, k, v, g), h,
        fa.bhtd_fwd_tile(h, t, t, dh=dh, group=h // hk,
                         block_diffusion=block), "the block-masked call")
    errs = {}
    for name, a, b in zip(("attn_o", "attn_dq", "attn_dk", "attn_dv"),
                          kernels(q, k, v, g), dense(q, k, v, g)):
        errs[name] = _rel(a, b)
        check(errs[name] <= KERNEL_REL_TOL,
              f"block-masked {name} off the dense composition by "
              f"{errs[name]:.4f} of its max (tolerance {KERNEL_REL_TOL})")
    pairs = {form or "fwd": fa.bhtd_pairs(t, t, tile, False, form=form,
                                          block_diffusion=block)
             for form in (None, "fused")}
    check(all(live == t_check * t_check + block * t_check
              for _, live in pairs.values()),
          f"the mask's live pairs are L^2 + B L: {pairs}")
    ms, _ = _traced_kernel_ms("chip_smoke_bd",
                              lambda: kernels(q, k, v, g), "attn.bhtd.")
    # (a trace needs the chip: the CPU tests run this phase through the
    # interpreters and read {})
    check(jax.default_backend() != "tpu"
          or sorted(ms) == ["attn.bhtd.bwd", "attn.bhtd.fwd"],
          f"kernels in the trace: {ms}")
    row = {**rows, "kernel_ms": ms,
           "pairs": {k_: list(v_) for k_, v_ in pairs.items()},
           "rel_err": {k_: round(v_, 5) for k_, v_ in errs.items()}}
    say(f"  block-masked kernels, ms a call at t{t} h{h} kv{hk} dh{dh}: "
        f"{ms}; pairs computed / live {row['pairs']}")
    say(f"  bd {row['rel_err']}")
    return row


def dsa_dispatch():
    """{"impl op pass shape": calls}: the sparse-attention indexer's
    calls lowered so far (pt_dsa_dispatch_total)."""
    from paddle_tpu.ops import dsa_ops

    return dsa_ops.dispatch_counts()


def keye_rows_hold(cfg, seq, lowered):
    """What ``keye-train-s16384``'s step must lower (keye_phase, 1.)."""
    from paddle_tpu.ops import dsa_ops
    from paddle_tpu.parallel import dsa_score

    dsa, attn, ropes = (lowered[k] for k in (
        "dsa", "attention", "rotary_embeddings"))
    n = cfg.num_hidden_layers
    cq, ck = (dsa_ops.chunk(seq, c) for c in (cfg.q_chunk_size,
                                              cfg.kv_chunk_size))
    impl = "kernel" if dsa_score.score_tile(
        cq, ck, cfg.indexer_num_heads, cfg.indexer_head_dim) else "xla"
    shape = (f"b1 t{seq} hI{cfg.indexer_num_heads} "
             f"dI{cfg.indexer_head_dim}")
    check(dsa.get(f"{impl} select fwd {shape} k{min(cfg.topk, seq)} "
                  f"cq{cq} ck{ck}") == n
          and sum(v for k, v in dsa.items() if " select " in k) == n,
          f"expected {n} dsa_select calls as {impl} at {shape}: {dsa}")
    loss = "kernel" if dsa_score.loss_tile(
        cq, ck, cfg.indexer_num_heads, cfg.indexer_head_dim) else "xla"
    check(dsa.get(f"{loss} loss fwd {shape} k0 cq{cq} ck{ck}") == n,
          f"expected {n} dsa_index_loss calls as {loss} at {shape}: {dsa}")
    for direction in ("fwd", "bwd"):
        check(sum(v for k, v in dsa.items()
                  if f" loss {direction} " in k) == n,
              f"expected {n} dsa_index_loss calls {direction}: {dsa}")
        rows = {k: v for k, v in attn.items() if f" {direction} " in k}
        check(sum(rows.values()) == n and all(
            k.startswith(f"bhtd {direction} b1 tq{seq} tk{seq} ")
            and k.endswith(" sel=operand") for k in rows),
            f"expected {n} attention calls {direction} under a selection "
            f"in the bhtd kernels (sel=operand), none dense: {attn}")
        turned = {k: v for k, v in ropes.items() if f" {direction} " in k}
        check(sum(turned.values()) == 2 * n and sum(
            v for k, v in turned.items() if "norm=head" in k) == n,
            f"expected {n} rotary embeddings {direction} with the heads' "
            f"gains and {n} of the indexer's: {ropes}")
    _one_backward_call(attn)
    _statistics_in_rows(attn)
    # the top-k's thresholds: dsa.topk.fwd's passes read a chunk's causal
    # prefix, XLA's ops the row's width (pt_dsa_topk_columns_total)
    want = {kind: n * a_row for kind, a_row in dsa_ops.columns(
        seq, min(cfg.topk, seq), cq, ck,
        dsa_score.topk_tile(cq, ck, seq)).items()}
    check(lowered["topk_columns"] == want,
          f"expected the selects' score columns {want} (walked: what the "
          f"thresholds' passes read, the causal prefixes where they are "
          f"dsa.topk.fwd's): {lowered['topk_columns']}")
    # (two query heads a forward step over ONE fetched block of K, V and
    # of the selection's words)
    _forward_tiles(attn, cfg.num_attention_heads, seq, dh=cfg.head_dim,
                   group=cfg.num_attention_heads // cfg.num_key_value_heads,
                   selected=True)


def keye_phase(seq=16384, t_check=2048, heads=(8, 2), dh=128, **overrides):
    """Learned sparse attention (models/keye.py): a lightning indexer's
    top-k read by the BHTD kernels as an operand.

    1. The cell ``keye-train-s16384``'s train step (four layers of
       Keye-VL-2.0-30B-A3B's language model at its published widths, 16
       of 128 experts held, an eighth of the vocabulary, bf16 AMP, Adam)
       is LOWERED, not run (perf/run.py runs it), and the dispatch
       counters are held to what the cell must lower: a ``dsa_select``
       a layer whose scores are ``dsa.score.fwd`` and whose thresholds'
       passes read the chunks' causal prefixes (``dsa.topk.fwd``:
       ``pt_dsa_topk_columns_total``), a ``dsa_index_loss``
       a layer that is ``dsa.loss.bwd`` and its grad op, one attention call a layer each way under the
       selection in the BHTD kernels (``sel=operand``; none ``dense``),
       the backward one call, the logsumexp in rows, and two rotary
       embeddings a layer each way (q and k with the heads' gains at the
       fed positions; the indexer's). ``overrides`` cut the config for
       the CPU tests.
    2. On the device, over ``t_check`` positions at ``heads`` of ``dh``:
       ``dsa_select`` with the kernel against XLA's ops (the share of
       the selection that agrees, the logsumexp rows), every row's count
       of keys, the selection against ``lax.top_k`` over the same scores
       position for position (one chunk without a top-k, three with
       ``dsa.topk.fwd``'s thresholds), the BHTD kernels under that selection and its live
       table, forward and the three gradients, against the dense
       composition, and ``dsa_index_loss`` with the kernel against XLA's
       ops a tile (the loss and its three gradients); the kernels' ms a
       call by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import dsa_ops
    from paddle_tpu.parallel import dsa_score
    from paddle_tpu.parallel import flash_attention as fa

    cfg, _, rows = lower_cell(
        "keye", seq, overrides, dsa=dsa_dispatch,
        attention=attention_dispatch, rotary_embeddings=rope_dispatch,
        topk_columns=dsa_ops.topk_columns,
        feeds={"input_ids": ((1, seq), "int32"),
               "labels": ((1, seq), "int32"),
               "position_ids": ((3, seq), "int32")})
    keye_rows_hold(cfg, seq, rows)

    # --- on the device ----------------------------------------------------
    (h, hk), t = heads, t_check
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    topk = min(cfg.topk, t // 4)
    cq, ck = (dsa_ops.chunk(t, c) for c in (cfg.q_chunk_size,
                                            cfg.kv_chunk_size))
    r = np.random.RandomState(11)
    qi, ki = (jnp.asarray(r.randn(1, n_heads, t, di), jnp.bfloat16)
              for n_heads in (hi, 1))
    w = jnp.asarray(r.randn(1, t, hi), jnp.bfloat16)
    attrs = {"scale": dsa_ops.index_scale(hi, di), "topk": topk,
             "q_chunk": cq, "kv_chunk": ck}

    def select():
        out = jax.jit(lambda *a: dsa_ops._dsa_select(
            {"QI": [a[0]], "KI": [a[1]], "W": [a[2]]}, attrs))(qi, ki, w)
        return [out[s][0] for s in ("Selected", "Live", "IndexLse")]

    selected, live, lse = select()
    mask = dsa_ops.unpack(selected, cq)
    counts = np.asarray(mask).sum(-1)[0]
    check((counts == np.minimum(np.arange(t) + 1, topk)).all(),
          f"every query chooses min(p + 1, {topk}) keys: "
          f"{counts[:4]} .. {counts[-4:]}")
    row = {**rows, "rel_err": {}, "kernel_ms": {}}
    kernel = dsa_score.score_tile(cq, ck, hi, di)
    w32 = w[0].astype(jnp.float32)
    scores = jnp.concatenate([
        dsa_score.score_rows(c, qi[0, :, c * cq:(c + 1) * cq], ki[0, 0],
                             w32[c * cq:(c + 1) * cq], attrs["scale"], ck)
        for c in range(t // cq)]) if kernel else dsa_ops.score_tile(
            qi[0], ki[0, 0], w32, attrs["scale"])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    row["top_k_agrees"] = float(np.mean(
        np.asarray(mask[0]) == np.asarray(jax.jit(
            dsa_ops.choose_by_sort, static_argnums=2)(scores, causal,
                                                      topk))))
    check(row["top_k_agrees"] == 1.0,
          f"the selection is lax.top_k's over the same scores at "
          f"{row['top_k_agrees']} of its positions, not every one")
    if kernel:
        keep, dsa_score.score_tile = dsa_score.score_tile, lambda *a: False
        try:
            plain = select()
        finally:
            dsa_score.score_tile = keep
        row["selection_agrees"] = float(np.mean(
            np.asarray(mask) == np.asarray(dsa_ops.unpack(plain[0], cq))))
        row["rel_err"]["index_lse"] = _rel(lse, plain[2])
        check(row["selection_agrees"] > 0.9999
              and row["rel_err"]["index_lse"] < 1e-4,
              f"dsa.score.fwd against XLA's ops: {row['selection_agrees']} "
              f"of the selection agrees, the logsumexp rows are off by "
              f"{row['rel_err']['index_lse']}")
    tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk)
    check(tile is not None and fa.bhtd_selected(
        h, t, t, dh=dh, group=h // hk, blocks=live.shape[1:]),
          f"the kernels do not take the call under a selection at t{t} "
          f"h{h} kv{hk}: tile {tile}, the selection's blocks "
          f"{live.shape[1:]}")
    q, k, v, g = (jnp.asarray(r.randn(1, n_heads, t, dh), jnp.bfloat16)
                  for n_heads in (h, hk, hk, h))

    @jax.jit
    def kernels(q, k, v, g):
        out, lse_ = fa.flash_attention_fwd(q, k, v, causal=True,
                                           selected=selected, live=live)
        return (out, *fa.flash_attention_bwd(
            q, k, v, None, None, out, lse_, g, causal=True,
            selected=selected, live=live))

    @jax.jit
    def dense(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
            q, k, v, None, dh ** -0.5, causal=True,
            selected=mask).astype(q.dtype), q, k, v)
        return (out, *vjp(g))

    _forward_on_its_tile(
        lambda *a: kernels.__wrapped__(*a)[0], (q, k, v, g), h,
        fa.bhtd_fwd_tile(h, t, t, dh=dh, group=h // hk, selected=True),
        "the call under a selection")
    for name, a, b in zip(("attn_o", "attn_dq", "attn_dk", "attn_dv"),
                          kernels(q, k, v, g), dense(q, k, v, g)):
        row["rel_err"][name] = _rel(a, b)
        check(row["rel_err"][name] <= KERNEL_REL_TOL,
              f"attention under a selection, {name}: off the dense "
              f"composition by {row['rel_err'][name]:.4f} of its max "
              f"(tolerance {KERNEL_REL_TOL})")
    # the indexer's loss under that selection, at the attention's own
    # logsumexp rows: the kernel against XLA's ops a tile
    loss_ins = {"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
                "Lse": [jax.jit(lambda: fa.flash_attention_fwd(
                    q, k, v, causal=True, selected=selected,
                    live=live)[1])()],
                "Selected": [selected], "IndexLse": [lse]}
    loss_attrs = {**attrs, "attn_scale": dh ** -0.5}

    def index_loss():
        out = jax.jit(lambda: dsa_ops._dsa_index_loss(loss_ins,
                                                      loss_attrs))()
        return [out[s][0] for s in ("Loss", "DQI", "DKI", "DW")]

    if dsa_score.loss_tile(cq, ck, hi, di):
        with_kernel = index_loss()
        keep, dsa_score.loss_tile = dsa_score.loss_tile, lambda *a: False
        try:
            plain = index_loss()
        finally:
            dsa_score.loss_tile = keep
        for name, a, b in zip(("index_loss", "dqi", "dki", "dw"),
                              with_kernel, plain):
            row["rel_err"][name] = _rel(a, b)
            check(row["rel_err"][name] <= KERNEL_REL_TOL,
                  f"dsa.loss.bwd against XLA's ops, {name}: off by "
                  f"{row['rel_err'][name]:.4f} of its max (tolerance "
                  f"{KERNEL_REL_TOL})")
    ms, _ = _traced_kernel_ms(
        "chip_smoke_keye",
        lambda: (select(), kernels(q, k, v, g), index_loss()), "")
    row["kernel_ms"] = {k_: v_ for k_, v_ in ms.items()
                        if k_.startswith(("attn.bhtd.", "dsa."))}
    # (a trace needs the chip: the CPU tests run this phase through the
    # interpreters and read {})
    check(jax.default_backend() != "tpu" or sorted(row["kernel_ms"]) == [
        "attn.bhtd.bwd", "attn.bhtd.fwd", "dsa.loss.bwd", "dsa.score.fwd",
        "dsa.topk.fwd"],
        f"kernels in the trace: {ms}")
    row["rel_err"] = {k_: round(v_, 6) for k_, v_ in row["rel_err"].items()}
    say(f"  keye kernels, ms a call at t{t} h{h} kv{hk} dh{dh}: "
        f"{row['kernel_ms']}; {row['rel_err']}")
    return row


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------

def build_train(cfg):
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = T.build(cfg)
        fluid.optimizer.Adam(1e-4).minimize(model["loss"])
    main._amp = True  # bf16 matmuls, f32 master weights
    return main, startup, model["loss"]


def _timed_steps(exe, program, feeds, steps, loss, scope):
    """``steps`` Executor.run calls -> (losses, seconds per call)."""
    import jax

    losses, secs = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        out = exe.run(program, feed=feeds[i % len(feeds)],
                      fetch_list=[loss], scope=scope, return_numpy=False)
        jax.block_until_ready(out)
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(out[0])))
    return losses, secs


def train_phase(cfg, batch=64, seq=256, steps=4, window_steps=8):
    """Startup, ``steps`` single steps, one compiled ``window_steps``
    window. Returns losses, first-call seconds per program and the
    attention dispatch it traced."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    before = attention_dispatch()
    main, startup, loss = build_train(cfg)
    scope = fluid.Scope()
    exe = fluid.Executor()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    jax.block_until_ready([scope.find_var(n) for n in scope.var_names()])
    startup_s = time.perf_counter() - t0

    feeds = [T.make_batch(cfg, batch, seq, seq, seed=s) for s in range(4)]
    losses, secs = _timed_steps(exe, main, feeds, steps, loss, scope)
    t0 = time.perf_counter()
    out = exe.run_steps(main, feed_list=feeds, steps=window_steps,
                        fetch_list=[loss], scope=scope, return_numpy=False)
    jax.block_until_ready(out)
    window_s = time.perf_counter() - t0
    window_loss = float(np.asarray(out[0]))
    exe.close()

    check(all(np.isfinite(x) for x in losses + [window_loss]),
          f"train loss not finite: steps {losses}, window {window_loss}")
    check(len(set(losses)) == len(losses) and window_loss != losses[-1],
          f"train loss is not changing: steps {losses}, window "
          f"{window_loss}")
    rep = {
        "batch": batch, "seq": seq, "n_layer": cfg.n_layer,
        "executor_device": repr(exe.device),
        "step_losses": [round(x, 5) for x in losses],
        "window_steps": window_steps, "window_loss": round(window_loss, 5),
        "first_call_s": {"startup": round(startup_s, 2),
                         "step": round(secs[0], 2),
                         "window": round(window_s, 2)},
        "later_step_s": [round(s, 4) for s in secs[1:]],
        "dispatch": _dispatch_since(before),
    }
    say(f"  train {rep}")
    return rep, losses


def recompile_phase(width=2048, depth=6, rows=(4096, 2048), steps=4):
    """A recompile in the middle of a profiled stretch: ``steps`` steady
    steps of a small program, then a feed of another shape. The trace
    must show ONE ``executor.first_call`` span on the host line, with
    ``cause=feed_signature``, and of the program's spans it must be the
    innermost at the start of the device's idle gap it made
    (perf/spans.py's ``idle_by_span``)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import jax_cache, layers
    from perf import spans, trace

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[width], dtype="float32")
        h = x
        for _ in range(depth):
            h = layers.fc(h, width, act="relu")
        loss = layers.mean(h)
        fluid.optimizer.SGD(1e-3).minimize(loss)
    main._amp = True
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    feeds = [{"x": jax.device_put(np.ones((n, width), np.float32))}
             for n in rows]

    def step(feed):
        return exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                       return_numpy=False)[0]

    jax.block_until_ready([step(feeds[0]) for _ in range(2)])
    trace_dir = jax_cache.fresh_dir("chip_smoke_recompile")
    jax.profiler.start_trace(trace_dir)
    try:
        # nothing waits between the calls: the device still runs the
        # last steady step when the host meets the miss
        outs = [step(feeds[0]) for _ in range(steps)]
        outs += [step(feeds[1]) for _ in range(2)]
        jax.block_until_ready(outs)
    finally:
        jax.profiler.stop_trace()
    exe.close()
    doc = spans.load(trace.find_xplane(trace_dir))
    firsts = [e for e in spans._dispatch_line(doc)
              if e[0] == "executor.first_call"]
    rep = {"first_calls": [[round(e[2] / 1e6, 3), e[3]] for e in firsts]}
    check(len(firsts) == 1
          and firsts[0][3].get("cause") == "feed_signature"
          and firsts[0][3].get("kind") == "step"
          and firsts[0][3].get("program") == f"program{main._uid}",
          f"expected one executor.first_call span with "
          f"cause=feed_signature on the host line: {rep['first_calls']}")
    reduced = spans.reduce(doc)
    check(reduced is not None and reduced["host"] is not None,
          f"the trace holds no device op to find an idle gap between "
          f"(host line: {rep['first_calls']})")
    # the host line also holds jax's and XLA's own events (PjitFunction,
    # the HLO passes): by those the gap is named as they nest, by the
    # program's spans alone as the program nests
    program = {"planes": [
        p if p["name"] != "/host:CPU" else dict(p, lines=[
            dict(ln, events=[e for e in ln["events"]
                             if e[0].startswith("executor.")])
            for ln in p["lines"]])
        for p in doc["planes"]]}
    idle = spans.reduce(program)["host"]["idle_by_span"]
    rep["idle_by_span_ms"] = [[k, round(v / 1e6, 3)] for k, v in idle]
    rep["idle_by_any_host_event_ms"] = [
        [k, round(v / 1e6, 3)]
        for k, v in reduced["host"]["idle_by_span"][:3]]
    say(f"  recompile {rep}")
    check(idle and idle[0][0] == "executor.first_call",
          f"the idle gap of the recompile is not named by its span: "
          f"{rep['idle_by_span_ms']}")
    return rep


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

def _streams(eng, group, max_new):
    """``group`` submitted together and run to idle -> per request its
    decode steps [(token, the greedy head's logit for it), ...], the
    step that ended it on EOS included. The logits ride the request
    trace (serving_trace.note_decode_step), so tracing must be on."""
    from paddle_tpu import monitor

    handles = [eng.submit(s, max_new_tokens=max_new) for s in group]
    eng.run_until_idle()
    steps = {}
    for ev in monitor.trace_events():
        if ev["name"] == "decode" and ev["cat"] == "request":
            a = ev["args"]
            steps.setdefault(a["req"], []).append(
                (a["step"], a["token"], a["logit"]))
    out = []
    for h_ in handles:
        check(h_.outcome in ("completed", "length") and h_.tokens,
              f"a request ended '{h_.outcome}' with "
              f"{len(h_.tokens)} tokens")
        got = [(tok, logit) for _, tok, logit in
               sorted(steps.get(h_.trace_id, []))]
        check([tok for tok, _ in got][:len(h_.tokens)] == list(h_.tokens),
              f"request {h_.trace_id}: its trace holds decode steps "
              f"{got}, its handle tokens {h_.tokens}")
        out.append(got)
    return out


def _tokens(stream):
    return [tok for tok, _ in stream]


def _parting(a, b):
    """Two greedy streams of one request -> (index of the first step
    whose tokens differ, or None; the largest relative difference of
    the two heads' logits over the steps before it; their relative
    difference at it, or None)."""
    def rel(i):
        return abs(a[i][1] - b[i][1]) / max(abs(a[i][1]), abs(b[i][1]), 1e-6)

    n = min(len(a), len(b))
    first = next((i for i in range(n) if a[i][0] != b[i][0]),
                 None if len(a) == len(b) else n)
    before = max(map(rel, range(n if first is None else first)), default=0.0)
    at = rel(first) if first is not None and first < n else None
    return first, before, at


def serve_phase(cfg, slots=8, src_len=32, max_len=57, max_new=24,
                src_lens=(32, 9, 17, 25, 12, 30, 21, 5)):
    """Requests of ``src_lens`` through ``serving.serve``. Three checks:

    1. isolation, on the path users run (default matmul precision): the
       requests submitted together to a ``slots``-slot engine each
       complete and equal the same request decoded ALONE on that
       engine, and the engine does not compile after its warm-up;
    2. geometry: each stream batched on a ``slots``-slot engine equals
       its solo decode on a ``slots=1`` engine. Two geometries are two
       XLA programs whose matmuls tile and reduce in another order, so
       the check runs where that leaves f32 rounding only — both engines
       under ``default_matmul_precision("highest")``. A mask, KV-cache
       or slot defect tied to the geometry fails here at any precision;
    3. at default precision (one bf16 MXU pass) the two geometries'
       logits differ by bf16 rounding and a greedy near-tie may flip
       (on the v5e two of the eight streams part). Then the streams
       legitimately differ from there on; checked instead: the heads'
       logits agree within SERVE_LOGIT_REL_TOL on every step before the
       parting AND at it, i.e. the flip was a tie inside the rounding,
       not another distribution."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import monitor, serving
    from paddle_tpu.models import transformer as T

    check(monitor.trace_active(),
          "serve_phase reads per-token logits from the request trace: "
          "set the telemetry and trace_dir flags")
    before = attention_dispatch()
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        T.build(cfg, is_test=True)
    fluid.Executor().run(startup, scope=scope)
    r = np.random.RandomState(17)
    srcs = [r.randint(2, cfg.src_vocab_size, (n,)).astype(np.int64)
            for n in src_lens]

    def engine(n_slots):
        return serving.serve(cfg, scope, slots=n_slots, src_len=src_len,
                             max_len=max_len)

    def solo_on(eng):
        return [_streams(eng, [s], max_new)[0] for s in srcs]

    # 1. isolation at default precision
    eng = engine(slots)
    t0 = time.perf_counter()
    warm = eng.submit(srcs[0], max_new_tokens=2)
    eng.run_until_idle()
    warm_s = time.perf_counter() - t0
    check(warm.outcome in ("completed", "length"),
          f"warm-up request ended '{warm.outcome}'")
    misses = _cache_misses()
    solo = solo_on(eng)
    together = _streams(eng, srcs, max_new)
    fresh = _cache_misses() - misses
    eng.close()
    check(fresh == 0, f"executor compiled {fresh} time(s) after warm-up")
    for i, (a, b) in enumerate(zip(together, solo)):
        check(_tokens(a) == _tokens(b),
              f"request {i} (src len {src_lens[i]}): batched stream "
              f"{_tokens(a)} != its solo stream on the same engine "
              f"{_tokens(b)}")

    # 2. geometry at "highest" precision: batched on `slots` == solo on 1
    with jax.default_matmul_precision("highest"):
        eng = engine(slots)
        hi_together = _streams(eng, srcs, max_new)
        eng.close()
        eng = engine(1)
        hi_solo = solo_on(eng)
        eng.close()
    hi_diff = 0.0
    for i, (a, b) in enumerate(zip(hi_together, hi_solo)):
        first, diff, at = _parting(a, b)
        check(first is None,
              f"request {i} (src len {src_lens[i]}) at highest matmul "
              f"precision: its stream batched on {slots} slots "
              f"{_tokens(a)} != its solo stream on a 1-slot engine "
              f"{_tokens(b)}; first differing step {first}, logits there "
              f"differ by {at}, before it by at most {diff:.2e}")
        hi_diff = max(hi_diff, diff)

    # 3. the same pair of geometries at default precision
    eng = engine(1)
    solo_1 = solo_on(eng)
    eng.close()
    partings, lo_diff = [], 0.0
    for i, (a, b) in enumerate(zip(together, solo_1)):
        first, diff, at = _parting(a, b)
        lo_diff = max(lo_diff, diff)
        if first is not None:
            partings.append({"request": i, "step": first,
                             "rel_logit_diff": at})
        check(diff <= SERVE_LOGIT_REL_TOL
              and (at is None or at <= SERVE_LOGIT_REL_TOL),
              f"request {i} (src len {src_lens[i]}): {slots}-slot and "
              f"1-slot engines disagree beyond bf16 rounding — logits "
              f"differ by {diff:.4f} of their size before step {first} and "
              f"by {at} at it (tolerance {SERVE_LOGIT_REL_TOL}); streams "
              f"{_tokens(a)} vs {_tokens(b)}")
    rep = {
        "slots": slots, "src_len": src_len, "max_len": max_len,
        "requests": len(srcs),
        "decode_steps": [len(t) for t in together],
        "equal_to_solo_same_engine": True,
        "compiles_after_warmup": fresh,
        "equal_to_solo_1slot_highest": True,
        "rel_logit_diff_vs_1slot": {
            "highest": float(f"{hi_diff:.3e}"),
            "default": float(f"{lo_diff:.3e}")},
        "partings_vs_1slot_default": partings,
        "warmup_s": round(warm_s, 2),
        "dispatch": _dispatch_since(before),
    }
    say(f"  serve {rep}")
    return rep


# ---------------------------------------------------------------------------
# phase 4: data parallel over every visible device
# ---------------------------------------------------------------------------

def dp_phase(cfg, one_chip_losses, batch=64, seq=256):
    """The train program of phase 2 under with_data_parallel over all
    devices, same seed and feeds: the losses must match the one-chip
    steps (as closely as the masks allow: DP_LOSS_REL_TOL), state must
    span the mesh, each feed shard is batch/n."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    n = len(jax.devices())
    check(batch % n == 0, f"global batch {batch} does not split over {n}")
    before = attention_dispatch()
    main, startup, loss = build_train(cfg)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    batch_sharding = NamedSharding(compiled.mesh, P("data"))
    feeds = []
    for s in range(4):
        fd = {k: jax.device_put(v, batch_sharding) for k, v in
              T.make_batch(cfg, batch, seq, seq, seed=s).items()}
        for k, a in fd.items():
            shard = a.addressable_shards[0].data.shape[0]
            check(shard == batch // n and len(a.addressable_shards) == n,
                  f"feed {k}: {len(a.addressable_shards)} shards of "
                  f"{shard} rows, expected {n} of {batch // n}")
        feeds.append(fd)
    losses, secs = _timed_steps(exe, compiled, feeds, len(one_chip_losses),
                                loss, scope)
    check(all(np.isfinite(x) for x in losses),
          f"data-parallel loss not finite: {losses}")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, one_chip_losses)]
    tol = DP_DROPOUT_LOSS_REL_TOL if cfg.dropout else DP_LOSS_REL_TOL
    check(max(rel) <= tol,
          f"data-parallel losses {losses} vs one-chip {one_chip_losses}: "
          f"relative difference {max(rel):.2e} > {tol}")
    spans = {name: len(scope.find_var(name).sharding.device_set)
             for name in scope.var_names()}
    narrow = {k: v for k, v in spans.items() if v != n}
    check(not narrow, f"state arrays not spanning {n} devices: {narrow}")
    exe.close()
    rep = {
        "devices": n, "global_batch": batch, "feed_shard_rows": batch // n,
        "step_losses": [round(x, 5) for x in losses],
        "one_chip_losses": [round(x, 5) for x in one_chip_losses],
        "max_rel_diff": float(f"{max(rel):.3e}"), "rel_tol": tol,
        "state_arrays": len(spans),
        "first_step_s": round(secs[0], 2),
        "device_bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()],
        "dispatch": _dispatch_since(before),
    }
    say(f"  dp {rep}")
    return rep


# ---------------------------------------------------------------------------
# the chip run
# ---------------------------------------------------------------------------

class IrDump:
    """The StableHLO modules jax handed to the compiler
    (``jax_dump_ir_to``): the compiled programs themselves, not a
    re-lowering. ``take()`` returns {module file: [operand/result types
    of each Pallas custom call in it]} for everything dumped since the
    last call."""

    def __init__(self, path):
        import jax

        self.path = path
        self.seen = set()
        jax.config.update("jax_dump_ir_to", path)

    def take(self):
        new = sorted(set(os.listdir(self.path)) - self.seen)
        self.seen.update(new)
        out = {}
        for name in new:
            with open(os.path.join(self.path, name), errors="replace") as f:
                # "... @tpu_custom_call(...) {config} : (types) -> types loc(..)"
                out[name] = [
                    line.rsplit("} : ", 1)[-1].split(" loc(")[0]
                    for line in f if "@tpu_custom_call" in line]
        return out


def _pallas_calls(modules, fn_name):
    """(most Pallas custom calls in one dumped module of jitted
    ``fn_name``, the types of that module's first call)."""
    calls = max((c for name, c in modules.items()
                 if f"_jit_{fn_name}_" in name), key=len, default=[])
    return len(calls), (calls[0] if calls else None)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform "
              f"'{dev.platform}' ({jax.devices()}); it does not run "
              f"anywhere else", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    import importlib.metadata

    import jaxlib

    from paddle_tpu import flags, jax_cache
    from paddle_tpu.parallel import flash_attention as fa

    n_dev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    cache_dir = jax_cache.configure()
    say(f"chip_smoke: device {device}, versions {versions}, "
        f"jax cache {cache_dir}")
    check(fa._INTERPRET is False,
          "flash_attention._INTERPRET is set: kernels would not be real")
    # telemetry: the attention dispatch record; the trace: per-token
    # logits of served requests (serve_phase)
    flags.set_flags({"telemetry": True, "trace_dir": os.path.join(
        os.path.dirname(REPORT_PATH), "chip_smoke_trace")})
    ir = IrDump(jax_cache.fresh_dir("chip_smoke_ir"))
    report = {"device": device, "versions": versions,
              "jax_cache_dir": cache_dir, "phase_s": {}}

    def phase(name, fn, *args, **kw):
        say(f"[{name}]")
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        report["phase_s"][name] = round(time.perf_counter() - t0, 1)
        say(f"[{name}] passed in {report['phase_s'][name]} s (observation)")
        return out, ir.take()

    report["attention_pairs"] = attention_pairs()
    say(f"  score pairs a head computed / visible at the cells' BHTD "
        f"calls: {report['attention_pairs']}")
    # 1. kernels: every case really is a Pallas call
    report["kernels"], _ = phase("kernels", kernel_phase)
    # did this machine compile them or read them? jax's own account
    report["compile_stages_after_kernels"] = compile_stages()
    say(f"  compile stages (s) and cache outcomes so far: "
        f"{report['compile_stages_after_kernels']}")
    for row in report["kernels"]:
        check(row["pallas_calls"] >= 1,
              f"kernel case {row['family']} t{row['t']} lowered without a "
              f"Pallas custom call")
    report["moe_kernels"], mods = phase("moe_kernels", moe_phase)
    n_moe, first_call = _pallas_calls(mods, "step_fn")
    report["moe_kernels"]["pallas_calls"] = n_moe
    say(f"  moe layer module: {n_moe} Pallas custom calls, the first "
        f"{first_call}")
    check(n_moe == 10, f"the layer's module holds {n_moe} Pallas custom "
          f"calls, expected its nine grouped matmuls and the tokens' "
          f"gradient's pairs.sum.dispatch_grad")

    report["moe_held"], _ = phase("moe_held", moe_held_phase)
    report["gdn"], _ = phase("gdn", gdn_phase)
    report["mla"], _ = phase("mla", mla_phase)
    report["ssm"], _ = phase("ssm", ssm_phase)
    report["mamba2"], _ = phase("mamba2", mamba2_phase)
    report["sconv"], _ = phase("sconv", sconv_phase)
    report["bd"], _ = phase("bd", bd_phase)
    report["kda"], _ = phase("kda", kda_phase)
    report["xing4"], _ = phase("xing4", xing4_phase)
    report["keye"], _ = phase("keye", keye_phase)
    report["rope"], _ = phase("rope", rope_phase)
    report["loss_head"], _ = phase("loss_head", loss_head_phase)

    # 2. train: the step and the window contain the kernels, and no
    # attention call fell to the dense composition
    cfg = transformer_base(max_length=258, dropout=0.1)
    (report["train"], losses), mods = phase("train", train_phase, cfg)
    per_step = 6 * cfg.n_layer  # 3 attentions a layer pair, fwd + bwd
    n_step, first_call = _pallas_calls(mods, "step_fn")
    n_window, _ = _pallas_calls(mods, "multi_fn")
    report["train"]["pallas_calls"] = {
        "step": n_step, "window": n_window, "first_call": first_call}
    say(f"  train step module: {n_step} Pallas custom calls (window "
        f"{n_window}), the first {first_call}")
    check(n_step >= per_step and n_window >= per_step,
          f"train step/window modules hold {n_step}/{n_window} Pallas "
          f"custom calls, expected >= {per_step} each")
    check(all(k.startswith("bthd_small ") for k in
              report["train"]["dispatch"]),
          f"train attention left the BTHD-small kernels: "
          f"{report['train']['dispatch']}")
    report["recompile"], _ = phase("recompile", recompile_phase)

    # 3. serve: prefill is a Pallas call at tq=tk=32; decode (tq=1) is
    # the dense composition BY DESIGN (no kernel family takes one query
    # row) — said here rather than left silent
    serve_cfg = transformer_base(max_length=256, dropout=0.0,
                                 label_smooth_eps=0.0)
    report["serve"], mods = phase("serve", serve_phase, serve_cfg)
    n_prefill, first_call = _pallas_calls(mods, "step_fn")
    report["serve"]["pallas_calls"] = {"prefill": n_prefill,
                                       "first_call": first_call}
    disp = report["serve"]["dispatch"]
    check(any(k.startswith("bthd_small fwd b1 tq32 tk32 ") for k in disp)
          and n_prefill >= 1,
          f"serving prefill did not take the Pallas kernel: dispatch "
          f"{disp}, {n_prefill} custom calls")
    check(all(" tq1 " in k for k in disp if k.startswith("dense ")),
          f"a serving attention other than decode went dense: {disp}")
    say(f"  note: prefill (tq=tk=32) runs the BTHD-small Pallas kernel "
        f"({n_prefill} custom calls, the first {first_call}); decode "
        f"attention (tq=1) runs the dense jnp composition by design")

    # 4. every visible chip
    if n_dev > 1:
        report["dp"], mods = phase("dp", dp_phase, cfg, losses)
        n_dp, first_call = _pallas_calls(mods, "step_fn")
        report["dp"]["pallas_calls"] = {"step": n_dp,
                                        "first_call": first_call}
        say(f"  dp step module: {n_dp} Pallas custom calls, per-device "
            f"operands of the first {first_call}")
        check(n_dp >= per_step,
              f"data-parallel step holds {n_dp} Pallas custom calls, "
              f"expected >= {per_step}")
        local = f" b{64 // n_dev} "
        check(all(k.startswith("bthd_small ") and local in k
                  and "replicated_over" not in k
                  for k in report["dp"]["dispatch"]),
              f"data-parallel attention is not per-device batch "
              f"{64 // n_dev} BTHD-small, computed once: "
              f"{report['dp']['dispatch']}")
        check(f"<{64 // n_dev}x256x512x" in (first_call or ""),
              f"the data-parallel attention call's operands are not the "
              f"per-device batch: {first_call}")
        check(all(b_ and b_ > 0 for b_ in
                  report["dp"]["device_bytes_in_use"]),
              f"a device reports no memory in use: "
              f"{report['dp']['device_bytes_in_use']}")

    report["total_s"] = round(time.perf_counter() - t_start, 1)
    os.makedirs(os.path.dirname(REPORT_PATH), exist_ok=True)
    with open(REPORT_PATH, "w") as f:
        json.dump(report, f, indent=1)
    say(f"chip_smoke: all phases passed in {report['total_s']} s "
        f"(observation); report at {REPORT_PATH}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
