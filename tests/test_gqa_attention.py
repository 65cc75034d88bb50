"""Grouped-query attention through the BHTD Pallas kernels (interpreter
mode on the CPU) against the dense composition, which copies K and V
for each head of a group: forward and the three gradients at groups of
1, 2 and 8 and heads of 128 and 256; the sdpa op's dispatch row; and a
rotary embedding over part of a head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags, monitor
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def qkv(h, hk, dh, t, seed=0, b=1):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, h, t, dh) * 0.3, jnp.float32),
            jnp.asarray(r.randn(b, hk, t, dh) * 0.3, jnp.float32),
            jnp.asarray(r.randn(b, hk, t, dh), jnp.float32),
            jnp.asarray(r.randn(b, h, t, dh), jnp.float32))


@pytest.mark.parametrize("edge_sub", [None, 64], ids=["whole", "sub64"])
@pytest.mark.parametrize("dh", [128, 256])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_kernels_agree_with_the_dense_composition(group, dh, edge_sub,
                                                  interpreted, monkeypatch):
    h, t, blk = 8, 256, 128
    if edge_sub:    # the diagonal's blocks walked in sub-tiles of 64 x 64
        monkeypatch.setattr(fa, "_EDGE_SUB", edge_sub)
    q, k, v, g = qkv(h, h // group, dh, t)
    tile = fa.bhtd_tile(h, t, t, blk, blk, dh=dh, group=group)
    # a group's heads go onto the grid, one a step; without groups the
    # tile is the one the parent picked
    assert tile == ((1, blk, blk) if group > 1
                    else fa._pick_tile(h, t, t, blk, blk, dh))
    scale = dh ** -0.5
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=True, q_block=blk,
                                          k_block=blk)
        want, vjp = jax.vjp(
            lambda q, k, v: fa._reference_attention_with_lse(
                q, k, v, None, scale, causal=True), q, k, v)
        dq, dk, dv = fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, q_block=blk,
            k_block=blk)
        wq, wk, wv = vjp((g, jnp.zeros_like(lse)))
    assert dk.shape == k.shape and dv.shape == v.shape
    np.testing.assert_allclose(out, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want[1], rtol=1e-5, atol=1e-5)
    # dk and dv sum over the group's query heads (up to 8 x 256 rows)
    for a, b, name in ((dq, wq, "dq"), (dk, wk, "dk"), (dv, wv, "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("group,blk,form", [(2, 128, "rows"),
                                            (8, 128, "rows"),
                                            (8, 64, "column")])
def test_grouped_forward_lse_as_rows_or_a_column(group, blk, form,
                                                 interpreted):
    """A group's heads go onto the grid one a step, and each writes its
    own logsumexp row (at blocks of 64: its column): [b, h, tq, 1] from
    ``flash_attention_fwd``, the dense composition's to float32
    rounding."""
    h, t, dh = 8, 256, 128
    q, k, v, _ = qkv(h, h // group, dh, t, seed=4)
    kw = dict(causal=True, q_block=blk, k_block=128)
    tile = fa.bhtd_tile(h, t, t, blk, 128, dh=dh, group=group)
    assert tile == (1, blk, 128) and fa.bhtd_stats_form(tile, t) == form
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        want_out, want_lse = fa._reference_attention_with_lse(
            q, k, v, None, dh ** -0.5, causal=True)
    assert lse.shape == (1, h, t, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, want_lse, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)


def test_non_causal_and_bias_per_query_head(interpreted):
    h, hk, dh, t = 4, 2, 128, 256
    q, k, v, g = qkv(h, hk, dh, t, seed=1)
    bias = jnp.asarray(np.random.RandomState(2).randn(1, h, t, t) * 0.5,
                       jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, bias, q_block=128,
                                          k_block=128)
        want, vjp = jax.vjp(
            lambda q, k, v: fa._reference_attention(q, k, v, bias,
                                                    dh ** -0.5), q, k, v)
        grads = fa.flash_attention_bwd(q, k, v, bias, None, out, lse, g,
                                       q_block=128, k_block=128)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(grads, vjp(g)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_what_grouped_heads_refuse():
    q, k, v, _ = qkv(6, 4, 64, 128)
    with pytest.raises(ValueError, match="do not divide"):
        fa.flash_attention_fwd(q, k, v)
    q, k, v, _ = qkv(4, 2, 64, 128)
    with pytest.raises(ValueError, match="dropout"):
        fa.flash_attention_fwd(q, k, v, seed=jnp.int32(1), p_drop=0.1)


def test_tile_of_the_qwen3_next_attention_layer():
    """16 query heads over 2 key/value heads of 256 at 8192 positions:
    one head a step at blocks of 512, inside both VMEM caps."""
    assert fa._pick_tile(16, 8192, 8192, None, None, 256, 8) == (1, 512, 512)
    assert fa._tile_fits(1, 512, 512, 256)
    # without groups the heads of a short call still share a step
    assert fa._pick_tile(2, 256, 256, None, None, 64, 1) == (2, 256, 256)
    assert fa._pick_tile(2, 256, 256, None, None, 64, 2) == (1, 256, 256)


def test_sdpa_op_names_the_key_value_heads(interpreted):
    q, k, v, g = qkv(4, 2, 128, 256, seed=3)
    attrs = {"scale": 128 ** -0.5, "layout": "bhtd", "causal": True,
             "is_test": True}
    from paddle_tpu.core import interp

    # (the counter is the process's: another file's rows may be in it)
    monitor.reset()
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)
    try:
        out = attention_ops._sdpa({"Q": [q], "K": [k], "V": [v]}, attrs)
        grads = attention_ops._sdpa_grad(
            {"Q": [q], "K": [k], "V": [v], "Out": out["Out"],
             "Lse": out["Lse"], "GRAD::Out": [g]}, attrs)
        counts = attention_ops.dispatch_counts(tiles=True)
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    # (blocks of 256: under the side from which a forward step takes
    # two heads, fa._FWD_PAIR_BLOCK)
    shape = "b1 tq256 tk256 h4 kv2 dh128 [hb1 bq256 bk256]"
    assert counts == {f"bhtd fwd {shape}": 1, f"bhtd bwd {shape}": 1}
    assert grads["GRAD::K"][0].shape == k.shape
    want = fa._reference_attention(q, k, v, None, 128 ** -0.5, causal=True)
    np.testing.assert_allclose(out["Out"][0], want, rtol=2e-2, atol=2e-3)


def test_rotary_over_part_of_a_head():
    """The first rotary_dim features turn exactly as a head of that
    width would, the others pass untouched, and the whole head is the
    default."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 4, 9, 32), jnp.float32)
    k = jnp.asarray(r.randn(2, 2, 9, 32), jnp.float32)   # fewer heads
    part = attention_ops._rotary_embedding(
        {"Q": [q], "K": [k]}, {"theta": 1e7, "rotary_dim": 8})
    full_of_slice = attention_ops._rotary_embedding(
        {"Q": [q[..., :8]], "K": [k[..., :8]]}, {"theta": 1e7})
    for p, f, x in ((part["QOut"][0], full_of_slice["QOut"][0], q),
                    (part["KOut"][0], full_of_slice["KOut"][0], k)):
        assert bool((p[..., :8] == f).all())
        assert bool((p[..., 8:] == x[..., 8:]).all())
        assert not bool((p[..., :8] == x[..., :8])[:, :, 1:].all())
    whole = attention_ops._rotary_embedding(
        {"Q": [q], "K": [k]}, {"theta": 1e7, "rotary_dim": 32})
    default = attention_ops._rotary_embedding({"Q": [q], "K": [k]},
                                              {"theta": 1e7})
    assert bool((whole["QOut"][0] == default["QOut"][0]).all())
    from perf.reference import qwen3next as ref
    np.testing.assert_allclose(part["QOut"][0], ref.rope(q, 1e7, 8),
                               rtol=1e-6, atol=1e-6)
