"""Nemotron-H (paddle_tpu/models/nemotron_h.py) on the CPU at tiny sizes
against the plain reference (perf/reference/nemotronh.py) on seeded
weights: the loss, the logits and every parameter's gradient for the cut
the benchmark runs (blocks 34-42 of 52, a share of the experts held) and
for a whole tiny model that holds every expert, so that the pattern
string is read where it is computed; that the shares ADD UP (the 16 held
shares' routed parts, with the shared expert counted once, are the uncut
layer's output); the reference's ablations each change what it
computes. The program's gradients come from ``append_backward``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, moved, reference, snapshot
from paddle_tpu import analysis, layers
from paddle_tpu.models import nemotron_h as M
from perf import flops_nemotronh
from perf.reference import nemotronh as ref

TINY = dict(vocab_size=50, hidden_size=32, mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48, n_routed_experts=8,
            num_experts_per_tok=3)
CUT = dict(num_hidden_layers=9, first_layer=34, held_experts=(2, 2))
WHOLE = dict(num_hidden_layers=7, hybrid_override_pattern="ME*MEM*")
REF_BASE = dict(
    {k: v for k, v in TINY.items() if k != "n_routed_experts"},
    layer_norm_epsilon=1e-5, conv_kernel=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, hybrid_override_pattern=M.PATTERN)


def ref_cfg(layout):
    cfg = dict(REF_BASE, **{k: v for k, v in layout.items()
                            if k != "held_experts"})
    first, count = layout.get("held_experts", (0, 8))
    cfg.update(held_first=first, n_routed_experts=count, router_experts=8)
    return cfg


# gains, biases, D, A_log and the routers' selection biases away from
# their initial values, so that every parameter matters; the projections
# larger, so that what a query sees and what a state keeps move the
# output; step sizes near 0.3, so that a state of 16 positions decays
# within the row
PERTURB = [((".scale", "_conv.b", "_mamba_d", "_mamba_a_log"), moved(0.2)),
           (("_router.bias",), drawn(0.1)),
           (("_mamba_dt.b",), lambda v, r: -1.0 + 0.2 * r.randn(*v.shape)),
           (("_colp.w", "_rowp.w", "_conv.w", "_up.w", "_down.w",
             "_router.w", "_tok_emb.w"), drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, **layout):
    cfg = M.NemotronHConfig(**TINY, **layout)
    return (cfg, *model_test.built(M, cfg, seed))


BLOCK = {
    "mamba2": ["norm.scale", "mamba_in_colp.w", "mamba_conv.w",
               "mamba_conv.b", "mamba_a_log", "mamba_d", "mamba_dt.b",
               "mamba_norm.scale", "mamba_out_rowp.w"],
    "moe": ["norm.scale", "moe_router.w", "moe_up.w", "moe_down.w",
            "moe_shared_up.w", "moe_shared_down.w"],
    "attn": ["norm.scale", "attn_qkv_colp.w", "attn_out_rowp.w"],
}


@pytest.mark.parametrize("layout,kinds", [
    (CUT, ["moe", "mamba2", "moe", "mamba2", "moe", "mamba2", "moe",
           "mamba2", "attn"]),
    (WHOLE, ["mamba2", "moe", "attn", "mamba2", "moe", "mamba2", "attn"]),
], ids=["blocks-34-42-of-52-held-2-of-8", "a-whole-model-of-7"])
def test_model_loss_logits_and_every_parameters_gradient(layout, kinds):
    cfg, main, startup, model, grads = built(11, **layout)
    first = layout.get("first_layer", 0)
    assert cfg.blocks == list(zip(range(first, first + len(kinds)), kinds))
    rcfg = ref_cfg(layout)
    assert ref.blocks(rcfg) == cfg.blocks
    assert flops_nemotronh.block_kinds(rcfg) == kinds
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    feed = M.make_batch(cfg, 2, 16, seed=9)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], *model["top_i"],
        *(g for _, g in grads)])
    want, want_loss, want_g = reference(ref, w, rcfg, feed)
    names = [p.name for p, _ in grads]
    expected = ["nemotronh_tok_emb.w", "final_norm.scale", "lm_head_colp.w"]
    expected += [f"blk{i}_{s}" for i, k in cfg.blocks for s in BLOCK[k]]
    assert sorted(names) == sorted(expected)
    held = layout.get("held_experts", (0, 8))[1]
    moe_at = next(i for i, k in cfg.blocks if k == "moe")
    assert w[f"blk{moe_at}_moe_up.w"].shape == (held, 32, 24)
    assert w[f"blk{moe_at}_moe_router.w"].shape == (32, 8)
    # float32 on both sides; the same mathematics in another order
    n_moe = kinds.count("moe")
    for a, b in zip(got[2:2 + n_moe], want["top_i"]):
        assert (np.sort(a, -1) == np.sort(np.asarray(b), -1)).all()
    np.testing.assert_allclose(got[0], want_loss, rtol=5e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=5e-4, atol=5e-5)
    g = dict(zip(names, got[2 + n_moe:]))
    for n in names:
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=3e-3,
                                   atol=2e-4 * scale + 1e-9, err_msg=n)


def test_defaults_are_the_published_model():
    cfg = M.nemotron_3_nano_30b_a3b()
    kinds = [k for _, k in cfg.blocks]
    assert len(kinds) == 52 and (kinds.count("mamba2"), kinds.count("moe"),
                                 kinds.count("attn")) == (23, 23, 6)
    assert [k for _, k in M.NemotronHConfig(
        num_hidden_layers=9, first_layer=34).blocks] == [
            "moe", "mamba2", "moe", "mamba2", "moe", "mamba2", "moe",
            "mamba2", "attn"]
    assert cfg.mamba_d_inner == 4096
    with pytest.raises(ValueError):
        M.NemotronHConfig(num_hidden_layers=9, first_layer=50)
    with pytest.raises(ValueError):
        M.NemotronHConfig(hybrid_override_pattern="M-M*")


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

N, D, F, E, K = 24, 16, 12, 16, 4


def moe_layer(held):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        out, *_ = layers.topk_moe(
            x, E, K, F, norm_topk_prob=True, name="m", held=held,
            gated=False, act="relu2", shared_d_ff=2 * F, shared_gate=False,
            shared_act="relu2", shared_gated=False, score="sigmoid",
            routed_scale=2.5, select_bias=True)
    return main, startup, out


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert a chip, sixteen chips: each share's output is its
    expert's part of the routed sum plus the shared expert (which every
    chip computes whole); the shares' routed parts and the shared expert
    ONCE are the layer that holds all sixteen."""
    r = np.random.RandomState(0)
    x = r.randn(N, D).astype("float32")
    w = {"m_router.w": r.randn(D, E) * 0.5, "m_router.bias": r.randn(E) * 0.1,
         "m_up.w": r.randn(E, D, F) * 0.3, "m_down.w": r.randn(E, F, D) * 0.3,
         "m_shared_up.w": r.randn(D, 2 * F) * 0.3,
         "m_shared_down.w": r.randn(2 * F, D) * 0.3}
    w = {k: v.astype("float32") for k, v in w.items()}

    def run(held):
        main, startup, out = moe_layer(held)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        for name, value in w.items():
            if held is not None and name in ("m_up.w", "m_down.w"):
                value = value[held[0]:held[0] + held[1]]
            assert np.asarray(scope.find_var(name)).shape == value.shape
            scope.set(name, jnp.asarray(value))
        return np.asarray(exe.run(main, feed={"x": x}, scope=scope,
                                  fetch_list=[out])[0])

    whole = run(None)
    shared = np.square(np.maximum(x @ w["m_shared_up.w"], 0)) @ w[
        "m_shared_down.w"]
    shares = [run((e, 1)) for e in range(E)]
    assert all(np.abs(s - shared).max() > 1e-3 for s in shares[:4])
    np.testing.assert_allclose(sum(s - shared for s in shares) + shared,
                               whole, rtol=1e-4, atol=1e-5)
    # and in fours, as a chip that holds four would
    fours = [run((e, 4)) for e in range(0, E, 4)]
    np.testing.assert_allclose(sum(s - shared for s in fours) + shared,
                               whole, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the reference's ablations
# ---------------------------------------------------------------------------


@functools.cache
def unablated():
    """(weights, ids, the reference's logits) every ablation is held
    against: one startup and one forward for all of them."""
    cfg, _, startup, _, _ = built(5, **CUT)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 4)
    w = snapshot(scope)
    ids = M.make_batch(cfg, 2, 16, seed=1)["input_ids"]
    with jax.default_matmul_precision("highest"):
        return w, ids, np.asarray(ref.forward(w, ref_cfg(CUT), ids)["logits"])


@pytest.mark.parametrize("ablation", ref.ABLATIONS)
def test_an_ablated_reference_is_another_model(ablation):
    w, ids, want = unablated()
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.forward(w, ref_cfg(CUT), ids,
                                       ablate=ablation)["logits"])
    scale = np.sqrt(np.mean(want ** 2))
    assert np.sqrt(np.mean((other - want) ** 2)) > 0.02 * scale
    if ablation == "no_carry":
        # the first chunk starts from nothing either way (the first
        # block is an expert layer, the second the first scan)
        np.testing.assert_allclose(other[:, :8], want[:, :8], rtol=1e-4,
                                   atol=1e-5)
    with pytest.raises(AssertionError):
        ref.forward(w, ref_cfg(CUT), ids, ablate="no_such")


# ---------------------------------------------------------------------------
# the gated norm's two new attributes
# ---------------------------------------------------------------------------


def test_gated_rms_norm_gates_first_and_normalises_by_groups():
    from paddle_tpu.core.registry import get_op_def

    r = np.random.RandomState(2)
    x, z = (r.randn(3, 5, 24).astype("float32") for _ in range(2))
    scale = (1 + 0.2 * r.randn(24)).astype("float32")
    op = get_op_def("gated_rms_norm").compute
    ins = {"X": [jnp.asarray(x)], "Z": [jnp.asarray(z)],
           "Scale": [jnp.asarray(scale)]}
    silu = z / (1 + np.exp(-z))

    def rms(v, size):
        g = v.reshape(v.shape[:-1] + (-1, size))
        return (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
                ).reshape(v.shape)

    cases = {(): rms(x, 24) * scale * silu,
             (("gate_first", True),): rms(x * silu, 24) * scale,
             (("group_size", 8),): rms(x, 8) * scale * silu,
             (("gate_first", True), ("group_size", 8)):
                 rms(x * silu, 8) * scale}
    for attrs, want in cases.items():
        got = op(ins, {"epsilon": 1e-5, **dict(attrs)})["Y"][0]
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6,
                                   err_msg=str(attrs))
    # a layer built without them has today's op: no new attribute
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        a = layers.data("a", shape=[5, 24], dtype="float32")
        layers.gated_rms_norm(a, a, epsilon=1e-6)
        layers.gated_rms_norm(a, a, gate_first=True, group_size=8)
        layers.gated_rms_norm(a, a, group_size=24)    # the whole axis
        with pytest.raises(ValueError):
            layers.gated_rms_norm(a, a, group_size=7)
    norms = [op_ for op_ in main.global_block().ops
             if op_.type == "gated_rms_norm"]
    keys = [sorted(k for k in op_.attrs if k in ("epsilon", "gate_first",
                                                 "group_size"))
            for op_ in norms]
    assert keys == [["epsilon"], ["epsilon", "gate_first", "group_size"],
                    ["epsilon"]]
