"""Granite-4.0-H (paddle_tpu/models/granite_hybrid.py) on the CPU at
tiny sizes against the plain reference (perf/reference/granitehybrid.py)
on seeded weights: the loss, the logits and every parameter's gradient
for a cut that keeps the published indices (layers 3-6: Mamba-2,
Mamba-2, attention, Mamba-2), with and without recomputation; each of
the four multipliers alone moves the logits; the head is the embedding
table; ``layer_types`` is read by published index; the reference's
ablations each change what it computes. The program's gradients come
from ``append_backward``."""

import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, moved, reference, snapshot
from paddle_tpu.models import granite_hybrid as M
from perf.reference import granitehybrid as ref

TINY = dict(vocab_size=50, hidden_size=32, shared_intermediate_size=48,
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8,
            mamba_chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2)
CUT = dict(first_layer=3, num_hidden_layers=4)
REF_CFG = dict(
    {k: v for k, v in TINY.items() if k != "mamba_chunk_size"}, **CUT,
    layer_types=list(M.LAYER_TYPES), rms_norm_eps=1e-5, mamba_n_groups=1,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0)

# gains, biases, D and A_log away from their initial values, so that
# every parameter matters; the projections larger, so that what a query
# sees (at a scale of 1 / 64!) and what a state keeps move the output;
# step sizes near 0.3, so that a state of 16 positions decays in the row
PERTURB = [((".scale", "_conv.b", "_mamba_d", "_mamba_a_log"), moved(0.2)),
           (("_mamba_dt.b",), lambda v, r: -1.0 + 0.2 * r.randn(*v.shape)),
           (("_attn_qkv_colp.w",), drawn(1.5)),
           (("_in_colp.w", "_rowp.w", "_conv.w"), drawn(0.3)),
           (("_tok_emb.w",), drawn(0.3))]

LAYER = {
    "mamba2": ["norm.scale", "mamba_in_colp.w", "mamba_conv.w",
               "mamba_conv.b", "mamba_a_log", "mamba_d", "mamba_dt.b",
               "mamba_norm.scale", "mamba_out_rowp.w"],
    "attn": ["norm.scale", "attn_qkv_colp.w", "attn_out_rowp.w"],
}
MLP = ["mlp_norm.scale", "mlp_in_colp.w", "mlp_out_rowp.w"]


def built(seed, **kw):
    cfg = M.GraniteHybridConfig(**{**TINY, **CUT, **kw})
    return (cfg, *model_test.built(M, cfg, seed))


def run_program(seed, recompute, **kw):
    cfg, main, startup, model, grads = built(seed, recompute=recompute, **kw)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    model_test.perturb(scope, seed, PERTURB)
    w = {k: jnp.asarray(v) for k, v in snapshot(scope).items()}
    feed = M.make_batch(cfg, 2, 16, seed=seed)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], model["last_logits"]]
        + [g for _, g in grads])
    return (cfg, main, w, feed, [np.asarray(g) for g in got],
            [p.name for p, _ in grads])


@pytest.mark.parametrize("recompute", ["none", "layer"])
def test_program_against_reference(recompute):
    cfg, main, w, feed, got, names = run_program(7, recompute)
    assert cfg.blocks == [(3, "mamba2"), (4, "mamba2"), (5, "attn"),
                          (6, "mamba2")]
    want_names = {M.TABLE, "final_norm.scale"} | {
        f"blk{i}_{n}" for i, kind in cfg.blocks for n in LAYER[kind] + MLP}
    assert set(names) == want_names == {
        p.name for p in main.all_parameters()}
    logits, loss, grads = reference(ref, w, REF_CFG, feed)
    assert abs(float(got[0]) - float(loss)) < 2e-6 * abs(float(loss))
    scale = float(np.abs(np.asarray(logits)).max())
    assert scale > 0.5, scale    # the logits say something
    assert np.abs(got[1] - np.asarray(logits)).max() < 2e-5 * scale
    np.testing.assert_array_equal(got[2], got[1][:, -M.LAST_POSITIONS:])
    for name, g in zip(names, got[3:]):
        r = np.asarray(grads[name])
        assert np.abs(r).max() > 0, name
        assert np.abs(g - r).max() < 3e-5 * np.abs(r).max() + 1e-9, name


def test_the_head_is_the_embedding_table():
    cfg, main, *_ = built(3)
    block = main.global_block()
    tables = [p.name for p in main.all_parameters()
              if p.shape[0] == cfg.vocab_size or cfg.vocab_size in p.shape]
    assert tables == [M.TABLE]
    lookup, = [op for op in block.ops if op.type == "lookup_table"]
    head, = [op for op in block.ops if op.type == "matmul"
             and op.namescope == "loss_head"]
    assert lookup.inputs["W"] == [M.TABLE] == head.inputs["Y"]
    # ... and its gradient is the sum of both uses'
    sums = [op for op in block.ops if op.type == "sum"
            and op.outputs["Out"] == [M.TABLE + "@GRAD"]]
    assert len(sums) == 1 and len(sums[0].inputs["X"]) == 2


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 6.0), ("attention_multiplier", 0.125),
    ("residual_multiplier", 0.3), ("logits_scaling", 4.0)])
def test_each_multiplier_alone_moves_the_logits(key, value):
    """... in the program as in the reference (which reads the same key):
    a builder that dropped one would agree with nothing."""
    base = run_program(5, "none")
    cfg, _, w, feed, got, _ = run_program(5, "none", **{key: value})
    assert getattr(cfg, key) == value
    scale = np.abs(base[4][1]).max()
    assert np.abs(got[1] - base[4][1]).max() > 0.02 * scale
    logits, loss, _ = reference(ref, w, dict(REF_CFG, **{key: value}), feed)
    assert np.abs(got[1] - np.asarray(logits)).max() < 2e-5 * scale
    assert abs(float(got[0]) - float(loss)) < 2e-6 * abs(float(loss))


def test_layer_types_is_read_by_published_index():
    assert M.LAYER_TYPES.count("attention") == 4
    assert [i for i, k in enumerate(M.LAYER_TYPES) if k == "attention"] == [
        5, 15, 25, 35]
    kinds = lambda **kw: [k for _, k in M.GraniteHybridConfig(
        **{**TINY, **kw}).blocks]
    assert kinds(first_layer=0, num_hidden_layers=10) == (
        ["mamba2"] * 5 + ["attn"] + ["mamba2"] * 4)
    assert kinds(first_layer=14, num_hidden_layers=3) == [
        "mamba2", "attn", "mamba2"]
    # a list of the caller's own, read the same way
    assert kinds(first_layer=1, num_hidden_layers=2, layer_types=[
        "mamba", "attention", "attention"]) == ["attn", "attn"]
    for bad in (dict(first_layer=39, num_hidden_layers=2),
                dict(layer_types=["mamba", "moe"], num_hidden_layers=2),
                dict(recompute="all")):
        with pytest.raises(ValueError):
            M.GraniteHybridConfig(**{**TINY, **bad})
    # the parameters' names and the scopes carry the published index
    _, main, *_ = built(3, first_layer=14, num_hidden_layers=3)
    scopes = {op.namescope.split("/")[0] for op in main.global_block().ops
              if op.namescope.startswith("blk")}
    assert scopes == {"blk14", "blk15", "blk16"}
    assert "blk15_attn_qkv_colp.w" in {p.name for p in main.all_parameters()}


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_each_ablation_changes_what_the_reference_computes(ablate):
    _, _, w, feed, got, _ = run_program(9, "none")
    # 16 heads of 16 so that 8 norm groups divide them; chunk 128 is the
    # row's 16 positions at a boundary of 8
    cfg = dict(REF_CFG)
    if ablate == "no_carry":
        old, ref.KERNEL_CHUNK = ref.KERNEL_CHUNK, 8
    try:
        run = model_test.highest(lambda w_: ref.forward(
            w_, cfg, feed["input_ids"], ablate=ablate))
        other = np.asarray(run(w))
    finally:
        if ablate == "no_carry":
            ref.KERNEL_CHUNK = old
    scale = np.abs(got[1]).max()
    assert np.abs(other - got[1]).max() > 0.01 * scale, ablate
