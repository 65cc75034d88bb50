"""Attention whose queries and keys are wider than its values (latent
attention: 192 over 128) through the BHTD Pallas kernels (interpreter
mode on the CPU) against the dense composition: forward and the three
gradients; at one width the call and the tile the parent makes; the sdpa
op's dispatch row; and a rotary embedding over interleaved pairs."""

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


def qkv(h, dk, dv, t, seed=0, b=1):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, h, t, dk) * 0.3, jnp.float32),
            jnp.asarray(r.randn(b, h, t, dk) * 0.3, jnp.float32),
            jnp.asarray(r.randn(b, h, t, dv), jnp.float32),
            jnp.asarray(r.randn(b, h, t, dv), jnp.float32))


# (heads, dk, dv, t, block): the tests' own small pair, the model's pair
# at a short row, and values WIDER than the keys
@pytest.mark.parametrize("h,dk,dv,t,blk", [
    (4, 24, 16, 256, 128), (2, 192, 128, 256, 128), (2, 64, 128, 256, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("edge_sub", [None, 64], ids=["whole", "sub64"])
def test_kernels_agree_with_the_dense_composition(h, dk, dv, t, blk, causal,
                                                  edge_sub, interpreted,
                                                  monkeypatch):
    if edge_sub:    # the diagonal's blocks walked in sub-tiles of 64 x 64
        monkeypatch.setattr(fa, "_EDGE_SUB", edge_sub)
    q, k, v, g = qkv(h, dk, dv, t)
    assert fa.bhtd_tile(h, t, t, blk, blk, dh=dk, dv=dv) is not None
    scale = dk ** -0.5      # the default: 1 / sqrt of the QUERY's width
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=causal,
                                          q_block=blk, k_block=blk)
        want, vjp = jax.vjp(
            lambda q, k, v: fa._reference_attention_with_lse(
                q, k, v, None, scale, causal=causal), q, k, v)
        grads = fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=causal, q_block=blk,
            k_block=blk)
        wants = vjp((g, jnp.zeros_like(lse)))
    assert out.shape == v.shape
    assert [x.shape for x in grads] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(out, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want[1], rtol=1e-5, atol=1e-5)
    for a, b, name in zip(grads, wants, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("h,blk,tile,form", [
    (1, 128, (1, 128, 128), "rows"), (2, 128, (2, 128, 128), "rows"),
    (2, 64, (2, 64, 128), "column")])
def test_latent_forward_lse_as_rows_or_a_column(h, blk, tile, form,
                                                interpreted):
    """Queries and keys of 192 over values of 128: the statistics'
    scratch is as wide as the lanes whatever the head's widths are, and
    the kernel writes the logsumexp as rows (one head a step, two) or,
    at a q block of 64, as the column; the dense composition's to
    float32 rounding."""
    dk, dv, t = 192, 128, 256
    q, k, v, _ = qkv(h, dk, dv, t, seed=6)
    kw = dict(causal=True, q_block=blk, k_block=128)
    assert fa.bhtd_tile(h, t, t, blk, 128, dh=dk, dv=dv) == tile
    assert fa.bhtd_stats_form(tile, t) == form
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        want_out, want_lse = fa._reference_attention_with_lse(
            q, k, v, None, dk ** -0.5, causal=True)
    assert lse.shape == (1, h, t, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, want_lse, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)


def test_custom_vjp_takes_two_widths(interpreted):
    q, k, v, g = qkv(2, 24, 16, 128, seed=5)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * g)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
            q, k, v, None, None, None, 0.0, 64, 64, True)), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: fa._reference_attention(
            q, k, v, None, 24 ** -0.5, causal=True)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("h,t,dh,group", [(16, 4096, 128, 1), (8, 256, 64, 1),
                                          (16, 8192, 256, 8), (12, 768, 64, 1)])
def test_at_one_width_the_tile_is_the_parents(h, t, dh, group):
    """``dv`` equal to ``dh`` (or left out) changes no tile: the cap
    that counts k, dk, v and dv counts 24 bytes an element as before."""
    base = fa._pick_tile(h, t, t, None, None, dh, group)
    assert fa._pick_tile(h, t, t, None, None, dh, group, dh) == base
    for hb, bq, bk in ((1, 512, 512), (h, 256, 256), (4, 128, 256)):
        assert fa._tile_fits(hb, bq, bk, dh) == fa._tile_fits(
            hb, bq, bk, dh, dh) == (24 * hb * bk * dh <= fa._KV_VMEM_BYTES
                                    and 4 * hb * bq * bk
                                    <= fa._SCORE_VMEM_BYTES)


def test_tile_of_the_latent_attention_call():
    """32 heads of 192 over 128 at 4096 positions: one head a step at
    blocks of 512, inside both VMEM caps; the heads of a short call
    still share a step."""
    assert fa._pick_tile(32, 4096, 4096, None, None, 192, 1, 128) \
        == (1, 512, 512)
    assert fa._tile_fits(1, 512, 512, 192, 128)
    assert fa._pick_tile(4, 256, 256, None, None, 24, 1, 16) == (4, 256, 256)


def test_sdpa_op_names_both_widths(interpreted):
    q, k, v, g = qkv(2, 192, 128, 256, seed=3)
    attrs = {"layout": "bhtd", "causal": True, "is_test": True}
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
        same = attention_ops._sdpa({"Q": [v], "K": [v], "V": [v]}, attrs)
        counts = attention_ops.dispatch_counts(tiles=True)
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = "b1 tq256 tk256 h2 dk192 dv128 [hb2 bq256 bk256]"
    assert counts == {
        f"bhtd fwd {shape}": 1, f"bhtd bwd {shape}": 1,
        # one width: the row the parent writes
        "bhtd fwd b1 tq256 tk256 h2 dh128 [hb2 bq256 bk256]": 1}
    assert out["Out"][0].shape == v.shape == same["Out"][0].shape
    assert [grads[f"GRAD::{s}"][0].shape for s in "QKV"] == [
        q.shape, k.shape, v.shape]
    # no scale attr: 1 / sqrt(192), the query's width
    want = fa._reference_attention(q, k, v, None, 192 ** -0.5, causal=True)
    np.testing.assert_allclose(out["Out"][0], want, rtol=2e-2, atol=2e-3)
    with pytest.raises(ValueError, match="layout='bhtd'"):
        attention_ops._sdpa(
            {"Q": [jnp.swapaxes(q, 1, 2)], "K": [jnp.swapaxes(k, 1, 2)],
             "V": [jnp.swapaxes(v, 1, 2)]}, dict(attrs, layout="bthd"))


def test_rotary_over_interleaved_pairs():
    """Features (2i, 2i + 1) of position p turn by p * theta^(-2i/d):
    an explicit 2 x 2 rotation of each pair; the key may be ONE head;
    the rotate-half form is the same rotation of other pairs."""
    r = np.random.RandomState(0)
    theta, d, t = 3.2e7, 8, 9
    q = r.randn(2, 4, t, d).astype(np.float32)
    k = r.randn(2, 1, t, d).astype(np.float32)       # one shared head
    got = attention_ops._rotary_embedding(
        {"Q": [jnp.asarray(q)], "K": [jnp.asarray(k)]},
        {"theta": theta, "interleaved": True})
    for x, y in ((q, got["QOut"][0]), (k, got["KOut"][0])):
        want = np.empty_like(x)
        for p in range(t):
            for i in range(d // 2):
                a = p * theta ** (-2.0 * i / d)
                rot = np.array([[np.cos(a), -np.sin(a)],
                                [np.sin(a), np.cos(a)]])
                want[..., p, 2 * i:2 * i + 2] = \
                    x[..., p, 2 * i:2 * i + 2] @ rot.T
        assert y.shape == x.shape
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # position 0 passes, and the form is not rotate-half's
    assert bool((got["QOut"][0][:, :, 0] == q[:, :, 0]).all())
    half = attention_ops._rotary_embedding(
        {"Q": [jnp.asarray(q)], "K": [jnp.asarray(k)]}, {"theta": theta})
    assert not np.allclose(half["QOut"][0], got["QOut"][0])
    # de-interleaved, rotated by halves and interleaved again: the same
    perm = np.r_[0:d:2, 1:d:2]
    back = attention_ops._rotary_embedding(
        {"Q": [jnp.asarray(q[..., perm])], "K": [jnp.asarray(k[..., perm])]},
        {"theta": theta})
    np.testing.assert_allclose(np.asarray(back["QOut"][0])[..., np.argsort(perm)],
                               got["QOut"][0], rtol=1e-5, atol=1e-6)
    # over the leading features of a wider head
    wide = attention_ops._rotary_embedding(
        {"Q": [jnp.asarray(np.concatenate([q, q], -1))], "K": [jnp.asarray(k)]},
        {"theta": theta, "interleaved": True, "rotary_dim": d})
    np.testing.assert_allclose(wide["QOut"][0][..., :d], got["QOut"][0],
                               rtol=1e-6)
    assert bool((wide["QOut"][0][..., d:] == q).all())
