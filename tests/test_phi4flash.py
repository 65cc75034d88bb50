"""Phi-4-mini-flash (paddle_tpu/models/phi4flash.py) on the CPU at tiny
sizes against the plain reference (perf/reference/phi4flash.py) on
seeded weights: the loss, the logits and every parameter's gradient for
the cut the benchmark runs (layers 14-19 of 32) and for a whole tiny
model (8 and 12 layers), so that the layer kinds' placement is tested
where it is computed; the gradient of what crosses layers (the key/value
source's K and V, the memory source's M) as the sum over its readers;
the tied table's gradient as the sum of the gather's and the head's;
differential attention (window, full, cross) against the dense
composition through the attention kernels' interpreter. The program's
gradients come from ``append_backward``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, highest, moved, reference, snapshot
from paddle_tpu import analysis, flags
from paddle_tpu.framework import grad_var_name
from paddle_tpu.models import phi4flash as M
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa
from perf import flops_phi4flash
from perf.reference import phi4flash as ref

TINY = dict(vocab_size=50, hidden_size=32, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=48, sliding_window=5,
            mamba_d_state=4, mamba_dt_rank=2)
CUT = dict(num_hidden_layers=6, first_layer=14, model_layers=32)
REF_BASE = dict(TINY, layer_norm_eps=1e-5, mb_per_layer=2)


# gains, biases, D and the lambda vectors away from their initial
# values, so that every parameter matters; the projections larger, so
# that what a query sees and what a state keeps move the output
PERTURB = [(lambda n: n.endswith((".scale", ".bias", ".b", "_ssm_d"))
            or "_lambda_" in n, moved(0.2)),
           (lambda n: n.endswith(("_colp.w", "_rowp.w", "_ssm_dt.w",
                                  "_conv.w")) or n == M.TABLE, drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, **layout):
    cfg = M.Phi4FlashConfig(**TINY, **layout)
    return (cfg, *model_test.built(M, cfg, seed))


def ref_cfg(layout):
    return dict(REF_BASE, **layout)


def run_against_reference(main, startup, model, grads, feed, cfg, extra=()):
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], *(g for _, g in grads), *extra])
    return (w, got, *reference(ref, w, cfg, feed))


LAYER = {
    "mamba": ["mixer_norm.scale", "mixer_norm.bias", "ssm_in_colp.w",
              "ssm_conv.w", "ssm_conv.b", "ssm_x_rowp.w", "ssm_dt.w",
              "ssm_a_log", "ssm_d", "ssm_dt.b", "ssm_out_rowp.w"],
    "gmu": ["mixer_norm.scale", "mixer_norm.bias", "gmu_in_colp.w",
            "gmu_out_rowp.w"],
    "swa": ["mixer_norm.scale", "mixer_norm.bias", "attn_qkv_colp.w",
            "attn_qkv_colp.b", "attn_lambda_lq1", "attn_lambda_lk1",
            "attn_lambda_lq2", "attn_lambda_lk2", "attn_subln.scale",
            "attn_out_rowp.w", "attn_out_rowp.b"],
    "mlp": ["mlp_norm.scale", "mlp_norm.bias", "mlp_up_colp.w",
            "mlp_down_rowp.w"],
}
LAYER["mamba_mem"] = LAYER["mamba"]
LAYER["full"] = LAYER["swa"]
LAYER["cross"] = [n.replace("qkv", "q") for n in LAYER["swa"]]


@pytest.mark.parametrize("layout,kinds", [
    (CUT, ["mamba", "swa", "mamba_mem", "full", "gmu", "cross"]),
    (dict(num_hidden_layers=8),
     ["mamba", "swa", "mamba", "swa", "mamba_mem", "full", "gmu", "cross"]),
], ids=["layers-14-19-of-32", "a-whole-model-of-8"])
def test_model_loss_logits_and_every_parameters_gradient(layout, kinds):
    cfg, main, startup, model, grads = built(11, **layout)
    placed = M.layer_kinds(cfg)
    first = layout.get("first_layer", 0)
    assert placed == list(zip(range(first, first + len(kinds)), kinds))
    assert [k for _, k in ref.layer_kinds(ref_cfg(layout))] == kinds
    assert flops_phi4flash.layer_kinds(ref_cfg(layout)) == kinds
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    feed = M.make_batch(cfg, 2, 16, seed=9)
    w, got, want, want_loss, want_g = run_against_reference(
        main, startup, model, grads, feed, ref_cfg(layout))
    names = [p.name for p, _ in grads]
    expected = [M.TABLE, "final_norm.scale", "final_norm.bias"]
    expected += [f"blk{i}_{s}" for i, k in placed
                 for s in LAYER[k] + LAYER["mlp"]]
    assert sorted(names) == sorted(expected)
    # no head of its own: the table is the head
    assert w[M.TABLE].shape == (50, 32)
    # float32 on both sides; the same mathematics in another order
    np.testing.assert_allclose(got[0], want_loss, rtol=5e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=5e-4, atol=5e-5)
    g = dict(zip(names, got[2:]))
    for n in names:
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=3e-3,
                                   atol=2e-4 * scale + 1e-9, err_msg=n)


def test_layer_kinds_of_the_published_model():
    kinds = [k for _, k in M.layer_kinds(M.phi4_mini_flash())]
    assert len(kinds) == 32
    assert kinds[:16] == ["mamba", "swa"] * 8
    assert kinds[16:18] == ["mamba_mem", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert M.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    # a cut that holds a reader holds its source
    with pytest.raises(AssertionError):
        M.layer_kinds(M.Phi4FlashConfig(num_hidden_layers=2, first_layer=18,
                                        model_layers=32))
    # the published sizes: 3.85B parameters
    z = flops_phi4flash
    cfg = dict(hidden_size=2560, num_hidden_layers=32, mb_per_layer=2,
               intermediate_size=10240, num_attention_heads=40,
               num_key_value_heads=20, vocab_size=200064)
    total = sum(z.mixer_params(cfg, k) + 3 * 2560 * 10240
                for k in z.layer_kinds(cfg)) + 2560 * 200064
    assert total == pytest.approx(3.85e9, rel=5e-3)


def loss_with_added(monkeypatch, w, cfg, feed, added, memory, full):
    """The reference's loss with ``added["memory"]`` [b, t, e] added to
    the scan output of layer ``memory`` and ``added["k1" | "k2" | "v"]``
    (heads first, as the program keeps them) to the keys and values of
    layer ``full``, before anything reads them. The reference has no
    hook for it: K and V move through a bias of the layer's projection
    that has a row a position, M through a wrapper of ``ref.scan`` that
    knows the layer's call by its D."""
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(
        x.shape[0], x.shape[2], -1)
    kv = jnp.concatenate([flat(added[k]) for k in ("k1", "k2", "v")], -1)
    bias = f"blk{full}_attn_qkv_colp.b"
    q = w[bias].shape[0] - kv.shape[-1]
    moved = dict(w, **{bias: w[bias] + jnp.pad(kv, [(0, 0), (0, 0), (q, 0)])})
    d, plain = w[f"blk{memory}_ssm_d"], ref.scan

    def scan(*args):
        y = plain(*args)
        return y + added["memory"] if args[5] is d else y

    with monkeypatch.context() as m:
        m.setattr(ref, "scan", scan)
        return ref.loss(moved, cfg, feed)


def test_what_crosses_layers_sums_its_readers_gradients(monkeypatch):
    """A whole tiny model of 12 layers: two GMUs read the memory source's
    M, the full layer and two cross layers read its K and V. The
    program's gradient of each is the reference's gradient of the loss
    by something ADDED to it (every reader sees the sum), and one
    element of each agrees with a finite difference."""
    layout = dict(num_hidden_layers=12)
    cfg, main, startup, model, grads = built(5, **layout)
    kinds = [k for _, k in M.layer_kinds(cfg)]
    assert kinds.count("gmu") == 2 and kinds.count("cross") == 2
    shared = {"memory": model["memory"], "k1": model["kv"][0],
              "k2": model["kv"][1], "v": model["kv"][2]}
    block = main.global_block()
    extra = [block.var(grad_var_name(v.name)) for v in shared.values()]
    feed = M.make_batch(cfg, 2, 16, seed=3)
    rcfg = ref_cfg(layout)
    w, got, _, _, _ = run_against_reference(main, startup, model, grads,
                                            feed, rcfg, extra)
    got = dict(zip(shared, got[-len(shared):]))
    # (jax's arrays: a traced batch of ids indexes the table under jit)
    w = {k: jnp.asarray(v) for k, v in w.items()}
    zeros = {"memory": np.zeros((2, 16, 64), np.float32),
             "k1": np.zeros((2, 1, 16, 8), np.float32),
             "k2": np.zeros((2, 1, 16, 8), np.float32),
             "v": np.zeros((2, 1, 16, 16), np.float32)}
    loss = lambda added, cfg_=rcfg: loss_with_added(
        monkeypatch, w, cfg_, feed, added, memory=6, full=7)
    want = highest(jax.grad(loss))(zeros)
    bumped = highest(loss)      # (one executable for the eight losses)
    for name, at in (("memory", (1, 7, 20)), ("k1", (0, 0, 5, 3)),
                     ("k2", (1, 0, 2, 6)), ("v", (0, 0, 9, 11))):
        assert got[name].shape == zeros[name].shape
        scale = np.abs(want[name]).max()
        assert scale > 0
        np.testing.assert_allclose(got[name], want[name], rtol=3e-3,
                                   atol=2e-4 * scale, err_msg=name)
        eps = 0.05
        bump = {k: v.copy() for k, v in zeros.items()}
        bump[name][at] = eps
        up = float(bumped(bump))
        bump[name][at] = -eps
        fd = (up - float(bumped(bump))) / (2 * eps)
        assert fd == pytest.approx(float(got[name][at]), rel=0.05,
                                   abs=0.02 * scale), name
    # one reader alone is not the sum: the last cross layer's part of
    # K's gradient is missing when the others are cut off
    alone = highest(jax.grad(lambda a: loss(
        a, dict(rcfg, num_hidden_layers=10, model_layers=12))))(zeros)
    assert np.abs(alone["k1"] - want["k1"]).max() > 1e-3 * np.abs(
        want["k1"]).max()


class SplitTable:
    """The tied table as the reference reads it, taken apart: ``rows``
    where it gathers, ``head`` where it takes the transpose."""

    def __init__(self, rows, head):
        self.rows, self.T = rows, head.T

    def __getitem__(self, ids):
        return self.rows[ids]


def test_the_tied_tables_gradient_is_the_gathers_plus_the_heads():
    cfg, main, startup, model, grads = built(7, **CUT)
    feed = M.make_batch(cfg, 2, 16, seed=4)
    rcfg = ref_cfg(CUT)
    w, got, _, _, want_g = run_against_reference(main, startup, model, grads,
                                                 feed, rcfg)
    names = [p.name for p, _ in grads]
    assert names.count(M.TABLE) == 1
    table_grad = got[2 + names.index(M.TABLE)]
    split = lambda rows, head: ref.loss(
        dict(w, **{M.TABLE: SplitTable(rows, head)}), rcfg, feed)
    gather, head = highest(jax.grad(split, (0, 1)))(w[M.TABLE], w[M.TABLE])
    scale = np.abs(table_grad).max()
    for part in (gather, head):
        assert np.abs(part).max() > 1e-3 * scale
    np.testing.assert_allclose(table_grad, gather + head, rtol=3e-3,
                               atol=2e-4 * scale)
    # only the rows of ids the batch holds get a gather's gradient
    unseen = np.setdiff1d(np.arange(50), feed["input_ids"].ravel())
    assert len(unseen) and not np.asarray(gather)[unseen].any()


@pytest.mark.parametrize("kind", ["swa", "full", "cross"])
def test_differential_attention_against_the_dense_composition(kind,
                                                              monkeypatch):
    """One attention layer's two maps through the BHTD kernels'
    interpreter at 64-wide heads over 128-wide values, a group of 2
    (the cell's widths at 4 / 2 pair-heads x 256 positions, blocks of
    128, a window of 100) and the combination, against the reference's
    explicit scores."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    h, hk, dh, t, d = 4, 2, 64, 256, 64
    cfg = dict(hidden_size=8 * dh, num_attention_heads=2 * h,
               num_key_value_heads=2 * hk, sliding_window=100,
               intermediate_size=4 * dh, layer_norm_eps=1e-5)
    r = np.random.RandomState({"swa": 1, "full": 2, "cross": 3}[kind])
    u = jnp.asarray(r.randn(1, t, 8 * dh), jnp.float32)
    p = "blk17"
    w = {f"{p}_attn_{'q' if kind == 'cross' else 'qkv'}_colp.w":
         0.05 * r.randn(8 * dh, (2 * h if kind == "cross"
                                 else 2 * h + 4 * hk) * dh),
         f"{p}_attn_out_rowp.w": 0.05 * r.randn(2 * h * dh, 8 * dh),
         f"{p}_attn_out_rowp.b": np.zeros(8 * dh),
         f"{p}_attn_subln.scale": 1 + 0.1 * r.randn(2 * dh)}
    w[next(iter(w)).replace(".w", ".b")] = 0.1 * r.randn(
        next(iter(w.values())).shape[1])
    w.update({f"{p}_attn_lambda_{s}": 0.1 * r.randn(dh)
              for s in ("lq1", "lk1", "lq2", "lk2")})
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    kv = tuple(jnp.asarray(r.randn(1, hk, t, width), jnp.float32)
               for width in (dh, dh, 2 * dh))
    with jax.default_matmul_precision("highest"):
        want, (k1, k2, v) = ref.attention(u, w, p, 17, cfg, kind,
                                          kv if kind == "cross" else None)
        # the program's side: the two sdpa ops and the combine op
        x = u @ next(iter(w.values())) + w[next(iter(w)).replace(".w", ".b")]
        q1, q2 = (ref.heads(x[..., i * h * dh:(i + 1) * h * dh], h, dh)
                  for i in range(2))
        attrs = {"scale": dh ** -0.5, "dropout_prob": 0.0, "is_test": True,
                 "layout": "bhtd", "causal": True}
        if kind == "swa":
            attrs["window"] = 100
        flags.set_flags({"telemetry": True})
        try:
            before = attention_ops.dispatch_counts()
            o1, o2 = (attention_ops._sdpa(
                {"Q": [q.astype(jnp.bfloat16)], "K": [k.astype(jnp.bfloat16)],
                 "V": [v.astype(jnp.bfloat16)]}, attrs)["Out"][0]
                for q, k in ((q1, k1), (q2, k2)))
            after = attention_ops.dispatch_counts()
        finally:
            flags.set_flags({"telemetry": False})
        o = attention_ops._diff_attention_combine(
            {"O1": [o1], "O2": [o2], "Scale": [w[f"{p}_attn_subln.scale"]],
             **{s.upper(): [w[f"{p}_attn_lambda_{s}"]]
                for s in ("lq1", "lk1", "lq2", "lk2")}},
            {"lambda_init": M.lambda_init(17), "epsilon": 1e-5})["Out"][0]
        assert o.dtype == jnp.bfloat16 and o.shape == (1, h, t, 2 * dh)
        got = (o.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            1, t, 2 * h * dh) @ w[f"{p}_attn_out_rowp.w"])
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 2e-2
    # both maps read the SAME V array: nothing is copied per map
    assert v.shape == (1, hk, t, 2 * dh)
