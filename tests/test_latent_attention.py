"""Attention whose queries and keys are wider than its values (latent
attention: 192 over 128) through the BHTD Pallas kernels (interpreter
mode on the CPU) against the dense composition: forward and the three
gradients; at one width the call and the tile the parent makes; the sdpa
op's dispatch row; a rotary embedding over interleaved pairs; and, since
PR 70, the queries and keys in TWO parts (QPe, and KPe's ONE shared
head) as operands of the kernels' own against the call assembled by
hand, the op's counted fallback, and ``decoder.latent_attention``'s
program against the builder that assembled."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.core import interp
from paddle_tpu.models import decoder
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa
from test_flash_attention import _pallas_calls


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


# --- q and k in two parts: QPe and KPe as operands of their own (PR 70) ---


@pytest.fixture
def one_head_a_step(interpreted, monkeypatch):
    """-> cap(features): the cap on a step's K and V blocks so low that
    ONE head of that many features (keys and values together) at key
    blocks of 128 fits and two do not: the heads of a short test row go
    onto the grid, as a long row's do, and the backward is the ONE call
    (``bhtd_parts`` asks for both)."""
    def cap(features):
        monkeypatch.setattr(fa, "_KV_VMEM_BYTES", 12 * 128 * features)
    return cap


def parts(h, hp, dh, r, dv, t, seed=0, dtype=jnp.float32, grid=None):
    """(q, k, v, q_pe, k_pe, g) of a call in two parts; ``grid``: values
    on multiples of it in +-2, so that every product of two and a sum of
    hundreds of them is exact in float32 whatever its order."""
    rs = np.random.RandomState(seed)

    def draw(*shape, s=0.4):
        x = rs.randn(*shape) * s
        if grid:
            x = np.clip(np.round(x / grid) * grid, -2.0, 2.0)
        return jnp.asarray(x, dtype)

    return (draw(1, h, t, dh), draw(1, h, t, dh), draw(1, h, t, dv, s=1.0),
            draw(1, h, t, r), draw(1, hp, t, r), draw(1, h, t, dv, s=1.0))


def by_hand(q, k, q_pe, k_pe):
    """The wide q and k the parent's builder assembled: a concat of q,
    the shared head copied up to the query heads and a concat of k."""
    h = q.shape[1]
    return (jnp.concatenate([q, q_pe], -1), jnp.concatenate(
        [k, jnp.repeat(k_pe, h // k_pe.shape[1], axis=1)], -1))


def both_ways(q, k, v, q_pe, k_pe, g, **kw):
    """((out, lse, dq, dk, dv, dq_pe, dk_pe) of the call in two parts,
    (out, lse, dq, dk, dv) of the wide call) through the kernels."""
    out, lse = fa.flash_attention_fwd(q, k, v, q_pe=q_pe, k_pe=k_pe, **kw)
    own = fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                 q_pe=q_pe, k_pe=k_pe, **kw)
    wq, wk = by_hand(q, k, q_pe, k_pe)
    wout, wlse = fa.flash_attention_fwd(wq, wk, v, **kw)
    wide = fa.flash_attention_bwd(wq, wk, v, None, None, wout, wlse, g, **kw)
    return (out, lse, *own), (wout, wlse, *wide)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("r", [8, 64])
@pytest.mark.parametrize("hp", [1, 4])
def test_two_parts_as_operands_are_the_call_assembled_by_hand(
        hp, r, causal, one_head_a_step):
    """Forward, Lse and all five gradients of a call given QPe
    [b, h, t, r] and KPe [b, hp, t, r] against the same call with q and
    k concatenated by hand (KPe's heads copied h / hp times): the same
    mathematics, a block's scores as two products in one float32 sum."""
    h, dh, dv, t, blk = 4, 24, 16, 384, 128
    one_head_a_step(dh + r + dv)
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t, seed=hp + r)
    kw = dict(causal=causal, q_block=blk, k_block=blk)
    assert fa.bhtd_parts(h, t, t, blk, blk, dh=dh, r=r, hp=hp, dv=dv,
                         itemsize=4)
    assert fa.bhtd_tile(h, t, t, blk, blk, dh=dh + r, dv=dv) == (1, blk, blk)
    with jax.default_matmul_precision("highest"):
        own, wide = both_ways(q, k, v, q_pe, k_pe, g, **kw)
    out, lse, dq, dk, dv_, dq_pe, dk_pe = own
    wout, wlse, wdq, wdk, wdv = wide
    assert [x.shape for x in own[2:]] == [
        q.shape, k.shape, v.shape, q_pe.shape, k_pe.shape]
    # the default scale is 1 / sqrt of BOTH widths, as the wide call's
    np.testing.assert_allclose(out, wout, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lse, wlse, rtol=1e-6, atol=2e-6)
    summed = wdk[..., dh:].reshape(1, hp, h // hp, t, r).sum(2)
    for a, b, name in ((dq, wdq[..., :dh], "dq"), (dk, wdk[..., :dh], "dk"),
                       (dv_, wdv, "dv"), (dq_pe, wdq[..., dh:], "dq_pe"),
                       (dk_pe, summed, "dk_pe")):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6, err_msg=name)


@pytest.mark.parametrize("edge_sub", [None, 64], ids=["whole", "sub64"])
def test_two_parts_at_bf16_are_the_wide_call_to_the_bit(edge_sub,
                                                        one_head_a_step,
                                                        monkeypatch):
    """bfloat16 operands on a coarse grid (every score exact in float32
    whatever the order of its sum): the call in two parts gives the wide
    call's bits, out, Lse and every gradient's slice; dk_pe, which the
    kernel writes a QUERY head in bf16 and XLA sums over the heads that
    share the key head, is the wide dk's slice summed the same way, the
    gradient of the parent's ``expand``."""
    if edge_sub:
        monkeypatch.setattr(fa, "_EDGE_SUB", edge_sub)
    h, hp, dh, r, dv, t, blk = 4, 1, 24, 8, 16, 256, 128
    one_head_a_step(dh + r + dv)
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t, seed=2,
                                   dtype=jnp.bfloat16, grid=0.25)
    own, wide = both_ways(q, k, v, q_pe, k_pe, g, causal=True, q_block=blk,
                          k_block=blk)
    out, lse, dq, dk, dv_, dq_pe, dk_pe = own
    wout, wlse, wdq, wdk, wdv = wide
    assert dk_pe.dtype == dq_pe.dtype == jnp.bfloat16
    copies = jnp.sum(wdk[..., dh:].reshape(1, hp, h // hp, t, r), axis=2)
    for a, b, name in ((out, wout, "out"), (lse, wlse, "lse"),
                       (dq, wdq[..., :dh], "dq"), (dk, wdk[..., :dh], "dk"),
                       (dv_, wdv, "dv"), (dq_pe, wdq[..., dh:], "dq_pe"),
                       (dk_pe, copies, "dk_pe")):
        assert a.dtype == b.dtype, name
        assert bool((a == b).all()), name


def test_two_parts_through_the_custom_vjp(one_head_a_step):
    """``flash_attention_with_lse(q_pe=, k_pe=)`` under jax.grad (the
    scan-over-layers path): the wide call's five gradients, with a
    cotangent for Lse too; a call without parts returns what it did."""
    h, hp, dh, r, dv, t = 2, 1, 24, 8, 16, 256
    one_head_a_step(dh + r + dv)
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t, seed=7)

    def loss(call):
        def f(*xs):
            out, lse = call(*xs)
            return jnp.sum(out * g) + 0.1 * jnp.sum(lse)
        return f

    def own(q, k, v, q_pe, k_pe):
        return fa.flash_attention_with_lse(
            q, k, v, None, None, None, 0.0, 128, 128, True, q_pe=q_pe,
            k_pe=k_pe)

    def wide(q, k, v, q_pe, k_pe):
        return fa.flash_attention_with_lse(
            *by_hand(q, k, q_pe, k_pe), v, None, None, None, 0.0, 128, 128,
            True)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(own), (0, 1, 2, 3, 4))(q, k, v, q_pe, k_pe)
        want = jax.grad(loss(wide), (0, 1, 2, 3, 4))(q, k, v, q_pe, k_pe)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_which_calls_the_kernels_take_in_two_parts(interpreted):
    """``bhtd_parts``: the cells' call (32 heads of 128 | 64 over 128 at
    4096, one shared head) at the tile and the form of the wide call;
    not where the heads share a step, the backward would be the pair,
    hp does not divide h, or the caller says the call is not plain; and
    the entry points refuse what it refuses."""
    cell = dict(dh=128, r=64, hp=1, dv=128)
    assert fa.bhtd_parts(32, 4096, 4096, **cell)
    assert fa.bhtd_tile(32, 4096, 4096, dh=192, dv=128) == (1, 512, 512)
    assert fa.bhtd_bwd_form(32, 4096, 4096, dh=192, dv=128) == "fused"
    assert not fa.bhtd_parts(32, 4096, 4096, **cell, plain=False)
    assert not fa.bhtd_parts(32, 4096, 4096, **dict(cell, hp=5))
    # a short row: all four heads in one step, the backward the pair
    assert fa.bhtd_tile(4, 256, 256, dh=32, dv=16) == (4, 256, 256)
    assert not fa.bhtd_parts(4, 256, 256, dh=24, r=8, hp=1, dv=16)
    # a row of 64k: the resident rows pass the ONE call's cap
    assert not fa.bhtd_parts(32, 65536, 65536, **cell)
    q, k, v, q_pe, k_pe, g = parts(4, 1, 24, 8, 16, 256)
    with pytest.raises(ValueError, match="bhtd_parts"):
        fa.flash_attention_fwd(q, k, v, causal=True, q_pe=q_pe, k_pe=k_pe)
    with pytest.raises(ValueError, match="come together"):
        fa.flash_attention_fwd(q, k, v, causal=True, q_pe=q_pe)


def test_a_call_in_one_part_keeps_its_operands_and_results(one_head_a_step):
    """Without QPe and KPe the two calls have the operands and results
    they had (seed, q, k, v [, do, lse, delta] -> out, lse | dq, dk,
    dv); in two parts each gains QPe and KPe, the backward dq_pe and a
    dk_pe [b, h, t, r] a QUERY head, and nothing [.., dh + r] exists."""
    h, hp, dh, r, dv, t = 4, 1, 24, 8, 16, 256
    one_head_a_step(dh + r + dv)
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t)
    wq, wk = by_hand(q, k, q_pe, k_pe)
    kw = dict(causal=True, q_block=128, k_block=128)

    def step(q, k, v, g, **pe):
        out, lse = fa.flash_attention_fwd(q, k, v, **kw, **pe)
        return fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                      **kw, **pe)

    def shapes(call):
        eqn = call[2]
        return ([x.aval.shape for x in eqn.invars],
                [x.aval.shape for x in eqn.outvars])

    fwd, bwd = map(shapes, _pallas_calls(step, wq, wk, v, g))
    s, wide, val, row = (2,), (1, h, t, dh + r), (1, h, t, dv), (1, h, 1, t)
    assert fwd == ([s, wide, wide, val], [val, row])
    assert bwd == ([s, wide, wide, val, val, row, row], [wide, wide, val])
    fwd, bwd = map(shapes, _pallas_calls(
        lambda *a: step(*a[:4], q_pe=a[4], k_pe=a[5]), q, k, v, g, q_pe,
        k_pe))
    part = (1, h, t, dh)
    assert fwd == ([s, part, part, val, q_pe.shape, k_pe.shape], [val, row])
    assert bwd == ([s, part, part, val, q_pe.shape, k_pe.shape, val, row,
                    row], [part, part, val, q_pe.shape, q_pe.shape])


# --- the op: the kernels' own operands, or ONE counted fallback ----------


def _sdpa_both_passes(ins, attrs, g):
    """(Out, the grad op's results, the dispatch counter's rows) of one
    sdpa call and its grad op under telemetry."""
    monitor.reset()
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)   # (a lowering: the rows count)
    try:
        out = attention_ops._sdpa(ins, attrs, rng=jax.random.PRNGKey(0))
        grads = attention_ops._sdpa_grad(
            dict(ins, Out=out["Out"], Lse=out["Lse"], **{"GRAD::Out": [g]}),
            attrs, rng=jax.random.PRNGKey(0))
        rows = [r["labels"] for r in monitor.snapshot()[
            "pt_attention_dispatch_total"]["values"] if r["value"]]
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    return out["Out"][0], grads, rows


_OP_CASES = {
    # (attrs beside the plain ones, a bias?) -> the label ``parts``
    "plain": ({}, False, "own"),
    "not_causal": ({"causal": False}, False, "own"),
    "window": ({"window": 100}, False, "assembled"),
    "bias": ({}, True, "assembled"),
    "kernels_off": ({"use_pallas": False}, False, "assembled"),
}


@pytest.mark.parametrize("case", sorted(_OP_CASES))
def test_sdpa_op_reads_the_parts_or_assembles_them_and_says_which(
        case, one_head_a_step):
    """A call given QPe and KPe: ``parts="own"`` on both rows where the
    fused kernels take it, ``parts="assembled"`` where a window, a bias
    or kernels switched off send it down today's path with q and k
    concatenated inside the op; either way the results are those of the
    call assembled by hand, GRAD::QPe and GRAD::KPe [b, hp, t, r]
    among them, and the rows' shape names the whole head."""
    extra, with_bias, want_parts = _OP_CASES[case]
    h, hp, dh, r, dv, t = 4, 1, 24, 8, 16, 256
    one_head_a_step(dh + r + dv)
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t, seed=len(case))
    attrs = {"layout": "bhtd", "causal": True, "is_test": True,
             "scale": 0.2, **extra}
    ins = {"Q": [q], "K": [k], "V": [v], "QPe": [q_pe], "KPe": [k_pe]}
    wq, wk = by_hand(q, k, q_pe, k_pe)
    wide = {"Q": [wq], "K": [wk], "V": [v]}
    if with_bias:
        bias = jnp.asarray(np.random.RandomState(1).randn(1, 1, 1, t),
                           jnp.float32)
        ins["Bias"], wide["Bias"] = [bias], [bias]
    with jax.default_matmul_precision("highest"):
        out, grads, rows = _sdpa_both_passes(ins, attrs, g)
        wout, wgrads, wrows = _sdpa_both_passes(wide, attrs, g)
    assert [r["parts"] for r in rows] == [want_parts] * 2
    assert all("parts" not in r for r in wrows)
    # but for the label, the rows are the wide call's
    assert [{k_: v_ for k_, v_ in r.items() if k_ != "parts"}
            for r in rows] == wrows
    assert f"h{h} dk{dh + r} dv{dv}" in rows[0]["shape"]
    np.testing.assert_allclose(out, wout, rtol=1e-5, atol=1e-6)
    wdq, wdk = wgrads["GRAD::Q"][0], wgrads["GRAD::K"][0]
    assert sorted(grads) == ["GRAD::K", "GRAD::KPe", "GRAD::Q", "GRAD::QPe",
                             "GRAD::V"]
    for slot, want in (("Q", wdq[..., :dh]), ("QPe", wdq[..., dh:]),
                       ("K", wdk[..., :dh]), ("V", wgrads["GRAD::V"][0]),
                       ("KPe", wdk[..., dh:].sum(1, keepdims=True))):
        got = grads[f"GRAD::{slot}"][0]
        assert got.shape == want.shape, slot
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6,
                                   err_msg=slot)


def test_sdpa_op_with_dropout_or_no_kernel_assembles(monkeypatch):
    """Attention dropout in training, and a backend without the kernels
    (this CPU, no interpreter): the fallback, counted; the default scale
    is 1 / sqrt of both widths."""
    h, hp, dh, r, dv, t = 2, 1, 24, 8, 16, 64
    q, k, v, q_pe, k_pe, g = parts(h, hp, dh, r, dv, t, seed=4)
    ins = {"Q": [q], "K": [k], "V": [v], "QPe": [q_pe], "KPe": [k_pe]}
    attrs = {"layout": "bhtd", "causal": True, "is_test": True}
    with jax.default_matmul_precision("highest"):
        out, grads, rows = _sdpa_both_passes(ins, attrs, g)
        want = fa._reference_attention(
            *by_hand(q, k, q_pe, k_pe), v, None, (dh + r) ** -0.5,
            causal=True)
    assert [(r["family"], r["parts"]) for r in rows] == [
        ("dense", "assembled")] * 2
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    assert grads["GRAD::KPe"][0].shape == k_pe.shape
    # (dropout takes keys as wide as the values, as it did: 8 | 8 over 16)
    q, k, v, q_pe, k_pe, g = parts(h, hp, 8, 8, 16, t, seed=5)
    _, grads, rows = _sdpa_both_passes(
        {"Q": [q], "K": [k], "V": [v], "QPe": [q_pe], "KPe": [k_pe]},
        dict(attrs, is_test=False, dropout_prob=0.1), g)
    assert [r["parts"] for r in rows] == ["assembled"] * 2
    assert grads["GRAD::QPe"][0].shape == q_pe.shape
    with pytest.raises(ValueError, match="come together"):
        attention_ops._sdpa({"Q": [q], "K": [k], "V": [v], "QPe": [q_pe]},
                            attrs)


# --- the builder: decoder.latent_attention against the one that assembled -

_TINY = dict(heads=4, nope=16, rope=8, dv=16, kv_lora_rank=16, hidden=32,
             eps=1e-6, q_lora_rank=24)


def _assembling_latent_attention(x, p, *, heads, nope, rope, dv, kv_lora_rank,
                                 hidden, eps, q_lora_rank, rope_theta):
    """``decoder.latent_attention`` as it stood before PR 70 (the
    parent's lines): the shared key head copied ``heads`` times and a
    wide q and k concatenated in front of an sdpa call in one part."""
    h, d = heads, decoder
    xn = d.rms_norm(x, eps, f"{p}_attn_norm")
    c_q = d.rms_norm(d.linear(xn, q_lora_rank, f"{p}_attn_q_a.w"), eps,
                     f"{p}_attn_q_a_norm")
    q = d.linear(c_q, h * (nope + rope), f"{p}_attn_q_b_colp.w")
    kva = d.linear(xn, kv_lora_rank + rope, f"{p}_attn_kv_a.w")
    c_kv, k_rope = layers.split(kva, [kv_lora_rank, rope], dim=-1)
    kv = d.linear(d.rms_norm(c_kv, eps, f"{p}_attn_kv_a_norm"),
                  h * (nope + dv), f"{p}_attn_kv_b_colp.w")
    q = d._heads_first(layers.reshape(q, [0, 0, h, nope + rope]))
    if rope_theta is not None:
        q_nope, q_rope = layers.split(q, [nope, rope], dim=-1)
    k_nope, v = layers.split(
        d._heads_first(layers.reshape(kv, [0, 0, h, nope + dv])),
        [nope, dv], dim=-1)
    k_rope = layers.unsqueeze(k_rope, [1])
    if rope_theta is not None:
        q_rope, k_rope = layers.rotary_embedding(
            q_rope, k_rope, theta=rope_theta, interleaved=True)
        q = layers.concat([q_nope, q_rope], axis=3)
    k = layers.concat([k_nope, layers.expand(k_rope, [1, h, 1, 1])], axis=3)
    ctx = layers.scaled_dot_product_attention(
        q, k, v, 1.0 / math.sqrt(nope + rope), name=f"{p}_attn_sdpa")
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, h * dv])
    return d.linear(ctx, hidden, f"{p}_attn_out_rowp.w")


def _latent_block(builder, rope_theta, x, weights=None):
    """(out, {parameter: gradient of sum(out^2)}, weights, the sdpa
    rows' ``parts``, the block's op types) of one latent block on x."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        out = builder(xv, "blk0", rope_theta=rope_theta, **_TINY)
        grads = append_backward(layers.reduce_sum(layers.square(out)))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    for n, w in (weights or {}).items():
        scope.set(n, jnp.asarray(w))
    weights = {p.name: np.asarray(scope.find_var(p.name)) for p, _ in grads}
    monitor.reset()
    flags.set_flags({"telemetry": True})
    try:
        with jax.default_matmul_precision("highest"):
            got = exe.run(main, feed={"x": x}, scope=scope,
                          fetch_list=[out, *(g for _, g in grads)])
        labels = [r["labels"].get("parts") for r in monitor.snapshot()[
            "pt_attention_dispatch_total"]["values"] if r["value"]]
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    return (got[0], dict(zip(weights, got[1:])), weights, labels,
            [op.type for op in main.global_block().ops])


@pytest.mark.parametrize("kernels", ["own", "assembled"])
@pytest.mark.parametrize("rope_theta", [3.2e7, None],
                         ids=["rotated", "not_rotated"])
def test_latent_attention_is_the_builder_that_assembled(rope_theta, kernels,
                                                        request):
    """A program built by ``decoder.latent_attention`` (no expand, no
    concat: the sdpa op takes the four parts), with and without a
    rotation, gives the output and every parameter's gradient of the
    parent's builder on the same weights: through the kernels' own
    operands (the interpreter, one head a step) and through the op's
    fallback (this CPU), which the dispatch rows tell apart."""
    if kernels == "own":
        request.getfixturevalue("one_head_a_step")(
            _TINY["nope"] + _TINY["rope"] + _TINY["dv"])
    t = 256
    x = np.random.RandomState(3).randn(1, t, _TINY["hidden"]).astype(
        np.float32)
    want, want_g, w, wlabels, wops = _latent_block(
        _assembling_latent_attention, rope_theta, x)
    got, got_g, _, labels, ops = _latent_block(
        decoder.latent_attention, rope_theta, x, w)
    assert labels == [kernels] * 2 and wlabels == [None] * 2
    assert {"expand", "concat"} <= set(wops)
    # (concat is split's gradient; none in the forward part)
    fwd = ops[:ops.index("scaled_dot_product_attention")]
    assert "expand" not in ops and "concat" not in fwd
    assert "expand" not in decoder.latent_attention.__code__.co_names
    assert "concat" not in decoder.latent_attention.__code__.co_names
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert sorted(got_g) == sorted(want_g)
    for n in want_g:
        scale = np.abs(want_g[n]).max()
        np.testing.assert_allclose(got_g[n], want_g[n], rtol=1e-4,
                                   atol=1e-5 * scale + 1e-9, err_msg=n)
