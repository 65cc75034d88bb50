"""Hardware attention-kernel checks: the real Pallas kernels on a real
TPU, skipped on CPU backends. Run on the chip in one pytest process:

    PT_TEST_TPU=1 python -m pytest tests/test_flash_attention_tpu.py -q

These pin what the Pallas interpreter cannot reach:
1. the forward (cq up to 256) and fused backward (cq=128) kernels
   regenerate bit-identical dropout masks from the absolute 128-row-block
   keying (incl. the u32->u16 bitcast shape convention), verified by
   comparing the kernel path against a dense reference fed the kernels'
   OWN masks (``fa.bthd_dropout_masks``);
2. hardware numerical parity of the single-block and K-blocked BTHD
   kernels (fwd + grads) against the dense composition;
3. in-kernel dropout (``prng_seed`` has no CPU lowering): determinism,
   keep-rate, and the exact-linear-in-v gradient, for the BHTD and the
   BTHD kernels.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as fa

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")


def _rand(shape, seed):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32) * 0.3)


@pytest.mark.parametrize("b,tq,tk,h,dh,pd", [
    (2, 256, 256, 3, 64, 0.3),     # single-block, cq=256 in both passes
    (1, 128, 1024, 2, 64, 0.3),    # K-blocked
    # PR 49, the three cells' heads and rate: transformer-base's 8 and
    # BERT's 12 heads of 64 (the scale a power of two, folded into q),
    # cross attention with tq != tk, and a scale that stays on the scores
    (2, 256, 256, 8, 64, 0.1),
    (2, 128, 128, 12, 64, 0.1),
    (2, 128, 256, 8, 64, 0.1),
    (2, 256, 256, 4, 32, 0.1),
])
def test_dropout_fwd_bwd_mask_consistency(b, tq, tk, h, dh, pd):
    seedv = 11
    r = np.random.RandomState(7)
    masks = fa.bthd_dropout_masks(b, tq, tk, h, dh, pd, seedv)
    q = jnp.asarray(r.normal(0, 1, (b, tq, h, dh))).astype(jnp.bfloat16)
    k = jnp.asarray(r.normal(0, 1, (b, tk, h, dh))).astype(jnp.bfloat16)
    v = jnp.asarray(r.normal(0, 1, (b, tk, h, dh))).astype(jnp.bfloat16)
    w = jnp.asarray(r.normal(0, 1, (b, tq, h, dh)).astype(np.float32))
    mask_bhqk = jnp.asarray(masks).transpose(0, 2, 1, 3)

    def fk(q, k, v):
        o, _ = fa.flash_attention_bthd_with_lse(
            q, k, v, None, jnp.int32(seedv), None, pd)
        return jnp.sum(o.astype(jnp.float32) * w)

    def fr(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(dh)
        p = jax.nn.softmax(s, -1) * mask_bhqk
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return jnp.sum(o.astype(jnp.float32) * w)

    l1, g1 = jax.value_and_grad(fk, (0, 1, 2))(q, k, v)
    l2, g2 = jax.value_and_grad(fr, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), atol=0.05)
    # keep / p_keep in float32: 1 / 0.9, not bf16's 1.109375
    kept = np.asarray(masks)[np.asarray(masks) > 0]
    np.testing.assert_array_equal(kept, np.float32(1.0 / (1.0 - pd)))


# CRC32 of np.packbits(bthd_dropout_masks(...) > 0), read on a v5e from
# the commit BEFORE PR 49 (my chip run, PR 49): the select keeps exactly
# the positions the bf16 mask chain kept, for the same seed.
_STREAM = {
    (2, 256, 256, 8, 64, 0.1, 5): 1760836917,
    (2, 128, 128, 12, 64, 0.1, 5): 2692083996,
    (2, 256, 256, 3, 64, 0.3, 11): 2881656903,
    (1, 128, 1024, 2, 64, 0.3, 11): 1113453391,
}


@pytest.mark.parametrize("case", sorted(_STREAM))
def test_dropout_stream_keeps_the_positions_it_kept_before(case):
    import zlib

    *shape, pd, seed = case
    kept = np.asarray(fa.bthd_dropout_masks(*shape, pd, seed)) > 0
    assert zlib.crc32(np.packbits(kept).tobytes()) == _STREAM[case]


@pytest.mark.parametrize("b,tq,tk,h,dh", [
    (2, 256, 256, 8, 64),
    (1, 256, 1024, 4, 64),
    (1, 1024, 768, 4, 64),
])
def test_hw_parity_vs_dense(b, tq, tk, h, dh):
    r = np.random.RandomState(3)
    q = jnp.asarray(r.normal(0, 1, (b, tq, h, dh))).astype(jnp.bfloat16)
    k = jnp.asarray(r.normal(0, 1, (b, tk, h, dh))).astype(jnp.bfloat16)
    v = jnp.asarray(r.normal(0, 1, (b, tk, h, dh))).astype(jnp.bfloat16)
    bias = jnp.asarray(r.normal(0, 1, (b, 1, tq, tk)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (b, tq, h, dh)).astype(np.float32))

    def f(q, k, v):
        o, _ = fa.flash_attention_bthd_with_lse(q, k, v, bias)
        return jnp.sum(o.astype(jnp.float32) * w)

    def ref(q, k, v):
        o = fa._reference_attention_bthd(q, k, v, bias, 1.0 / np.sqrt(dh))
        return jnp.sum(o.astype(jnp.float32) * w)

    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), atol=0.05)


@pytest.mark.parametrize("t,tile", [
    (4096, (1, 512, 512)), (768, (4, 256, 256)), (640, (16, 128, 128))])
def test_bhtd_causal_at_16_heads_of_128_matches_dense(t, tile):
    # OLMoE's attention (models/olmoe.py: [b, 16, 4096, 128], causal, no
    # bias), the first shape with heads of 128. All 16 heads in a step
    # left room for 128 x 128 blocks only (PR 28); the heads go onto the
    # grid and a step works on one head's 512 x 512 (PR 29). Where 512
    # does not divide t (a ring's quarter of 3072) the blocks halve until
    # they do, and the kernels still take the call.
    b, h, dh = 1, 16, 128
    assert fa.bhtd_tile(h, t, t, dh=dh) == tile
    r = np.random.RandomState(5)
    q, k, v = (jnp.asarray(r.normal(0, 1, (b, h, t, dh))).astype(
        jnp.bfloat16) for _ in range(3))
    w = jnp.asarray(r.normal(0, 1, (b, h, t, dh)).astype(np.float32))

    def f(q, k, v):
        o, _ = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref(q, k, v):
        o = fa._reference_attention(q, k, v, None, 1.0 / np.sqrt(dh),
                                    causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o1), g1 = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        q, k, v)
    (_, o2), g2 = jax.value_and_grad(ref, argnums=(0, 1, 2), has_aux=True)(
        q, k, v)
    # bf16 inputs and outputs on both sides, as the other parity cases
    for a, b_ in zip((o1, *g1), (o2, *g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), atol=0.05)


@pytest.mark.parametrize("t", [8192, 1024])
def test_grouped_query_attention_at_heads_of_256_matches_dense(t):
    # Qwen3-Next's attention layer (models/qwen3_next.py: q [b, 16, t,
    # 256], K and V [b, 2, t, 256], causal): one query head a grid step,
    # its key/value head picked by the index maps (q head // 8), dk and
    # dv summed over a group's 8 heads inside the dk/dv kernel. The dense
    # composition copies K and V eight times.
    b, h, hk, dh = 1, 16, 2, 256
    assert fa.bhtd_tile(h, t, t, dh=dh, group=h // hk) == (1, 512, 512)
    r = np.random.RandomState(6)
    q = jnp.asarray(r.normal(0, 0.5, (b, h, t, dh))).astype(jnp.bfloat16)
    k, v = (jnp.asarray(r.normal(0, 0.5, (b, hk, t, dh))).astype(
        jnp.bfloat16) for _ in range(2))
    w = jnp.asarray(r.normal(0, 1, (b, h, t, dh)).astype(np.float32))

    def f(q, k, v):
        o, _ = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref(q, k, v):
        # a query head at a time: 16 heads' [t, t] scores at 8192 are 4 GB
        def one(i):
            kv = i // (h // hk)
            return fa._reference_attention(
                q[:, i:i + 1], k[:, kv:kv + 1], v[:, kv:kv + 1], None,
                1.0 / np.sqrt(dh), causal=True)
        o = jnp.concatenate([one(i) for i in range(h)], 1)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o1), g1 = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o2), g2 = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert g1[1].shape == k.shape and g1[2].shape == v.shape
    for name, a, b_ in zip(("o", "dq", "dk", "dv"), (o1, *g1), (o2, *g2)):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        # bf16 on both sides; dk and dv sum 8 heads x up to 8192 rows
        np.testing.assert_allclose(a, b_, atol=0.02 * np.abs(b_).max() + 0.05,
                                   err_msg=name)


@pytest.mark.parametrize("t,window", [(8192, 4096), (4096, 1000)])
def test_windowed_attention_at_a_group_of_7_matches_dense(t, window):
    # SmallThinker's window layers (models/smallthinker.py: q [b, 28, t,
    # 128], K and V [b, 4, t, 128], causal, the last ``window``
    # positions): the grids' inner axis walks the band, the two-sided
    # mask runs on edge blocks only, dk and dv sum a group's 7 heads. A
    # window of 1000: no block divides it, and the band's width in
    # blocks differs from row to row.
    b, h, hk, dh = 1, 28, 4, 128
    assert fa.bhtd_tile(h, t, t, dh=dh, group=h // hk) == (1, 512, 512)
    r = np.random.RandomState(7)
    # (scores of unit variance: a softmax peaked enough that which keys
    # a query sees moves its output by far more than bf16 rounds it)
    q, k, v = (jnp.asarray(r.normal(0, 1.0, (b, n, t, dh))).astype(
        jnp.bfloat16) for n in (h, hk, hk))
    w = jnp.asarray(r.normal(0, 1, (b, h, t, dh)).astype(np.float32))

    def f(q, k, v):
        o, _ = fa.flash_attention_with_lse(q, k, v, causal=True,
                                           window=window)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref(q, k, v):
        def one(i):   # a query head at a time
            kv = i // (h // hk)
            return fa._reference_attention(
                q[:, i:i + 1], k[:, kv:kv + 1], v[:, kv:kv + 1], None,
                1.0 / np.sqrt(dh), causal=True, window=window)
        o = jnp.concatenate([one(i) for i in range(h)], 1)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o1), g1 = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o2), g2 = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert g1[1].shape == k.shape and g1[2].shape == v.shape
    for name, a, b_ in zip(("o", "dq", "dk", "dv"), (o1, *g1), (o2, *g2)):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        # bf16 on both sides; dk and dv sum 7 heads x up to 4096 rows
        np.testing.assert_allclose(a, b_, atol=0.03 * np.abs(b_).max(),
                                   err_msg=name)
    # and the window is computed: the causal call gives another result,
    # further from this one than this one is from the composition
    o3, _ = jax.jit(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, causal=True))(q, k, v)
    o1, o2, o3 = (np.asarray(o, np.float32)[:, :, window:]
                  for o in (o1, o2, o3))
    assert np.abs(o3 - o1).max() > 5 * np.abs(o2 - o1).max()


@pytest.mark.parametrize("t", [4096, 1024])
def test_latent_attention_at_192_over_128_matches_dense(t):
    # JoyAI-LLM-Flash's attention call (models/joyai_flash.py: q and k
    # [b, 32, t, 192], v and out [b, 32, t, 128], causal): one head a grid
    # step at blocks of 512, q and k read at their 192 (a lane and a
    # half, nothing padded in HBM), against the composition in float32
    # at "highest" precision, a head at a time.
    b, h, dk, dv = 1, 32, 192, 128
    assert fa.bhtd_tile(h, t, t, dh=dk, dv=dv) == (1, 512, 512)
    r = np.random.RandomState(8)
    q, k = (jnp.asarray(r.normal(0, 0.5, (b, h, t, dk))).astype(
        jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(r.normal(0, 0.5, (b, h, t, dv))).astype(jnp.bfloat16)
    w = jnp.asarray(r.normal(0, 1, (b, h, t, dv)).astype(np.float32))

    def f(q, k, v):
        o, _ = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref(q, k, v):
        # a head at a time: 32 heads' [t, t] float32 scores at 4096 are 2 GB
        def one(i):
            with jax.default_matmul_precision("highest"):
                return fa._reference_attention(
                    *(z[:, i:i + 1].astype(jnp.float32) for z in (q, k, v)),
                    None, 1.0 / np.sqrt(dk), causal=True)
        o = jnp.concatenate([one(i) for i in range(h)], 1)
        return jnp.sum(o * w), o

    (_, o1), g1 = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o2), g2 = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert o1.shape == v.shape and [g.shape for g in g1] == [
        q.shape, k.shape, v.shape]
    for name, a, b_ in zip(("o", "dq", "dk", "dv"), (o1, *g1), (o2, *g2)):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        # bf16 operands, probabilities and results against float32; dk
        # and dv sum up to 4096 rows
        np.testing.assert_allclose(a, b_, atol=0.02 * np.abs(b_).max() + 0.05,
                                   err_msg=name)


def test_latent_attention_in_two_parts_is_the_wide_call(capsys):
    # The latent cells' call since PR 70: q and k of 128 features, QPe
    # [b, 32, t, 64] and KPe's ONE head [b, 1, t, 64] as operands of the
    # kernels' own, against the wide call of [q | q_pe] and [k | k_pe
    # copied 32 times] through the same kernels: bf16 on both sides, the
    # same MXU passes, so out and every gradient's slice agree to a bf16
    # rounding of a sum in another order; dk_pe against the copies'
    # summed slice. Prints a call's ms each way.
    import time

    b, h, t, dh, r, dv = 1, 32, 4096, 128, 64, 128
    assert fa.bhtd_parts(h, t, t, dh=dh, r=r, hp=1, dv=dv)
    rs = np.random.RandomState(9)

    def draw(heads, width, s=0.5):
        return jnp.asarray(rs.normal(0, s, (b, heads, t, width))).astype(
            jnp.bfloat16)

    q, k, v, q_pe, k_pe = (draw(h, dh), draw(h, dh), draw(h, dv),
                           draw(h, r), draw(1, r))
    g = draw(h, dv, 1.0)

    @jax.jit
    def own(q, k, v, q_pe, k_pe, g):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=True, q_pe=q_pe,
                                          k_pe=k_pe)
        return (out, lse, *fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, q_pe=q_pe,
            k_pe=k_pe))

    @jax.jit
    def wide(q, k, v, q_pe, k_pe, g):
        wq = jnp.concatenate([q, q_pe], -1)
        wk = jnp.concatenate([k, jnp.tile(k_pe, (1, h, 1, 1))], -1)
        out, lse = fa.flash_attention_fwd(wq, wk, v, causal=True)
        dq, dk, dv_ = fa.flash_attention_bwd(wq, wk, v, None, None, out, lse,
                                             g, causal=True)
        return (out, lse, dq[..., :dh], dk[..., :dh], dv_, dq[..., dh:],
                jnp.sum(dk[..., dh:], axis=1, keepdims=True))

    args = (q, k, v, q_pe, k_pe, g)
    got, want = own(*args), wide(*args)
    ms = {}
    for name, f in (("own", own), ("wide", wide)):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            res = f(*args)
        jax.block_until_ready(res)
        ms[name] = (time.perf_counter() - t0) * 100.0
    with capsys.disabled():
        print(f"\nlatent call, fwd + bwd, ms: parts as operands "
              f"{ms['own']:.3f}, assembled by XLA in front {ms['wide']:.3f}")
    for name, a, b_ in zip(("out", "lse", "dq", "dk", "dv", "dq_pe", "dk_pe"),
                           got, want):
        assert a.shape == b_.shape and a.dtype == b_.dtype, name
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        np.testing.assert_allclose(a, b_, atol=0.01 * np.abs(b_).max(),
                                   err_msg=name)


# --- in-kernel dropout: determinism, keep-rate, exact-linear dv ---


def _assert_linear_in_v(f, v):
    """out is linear in v for a fixed dropout mask, so the analytic dv
    must equal the directional finite difference. The dot is
    cancellation-heavy: the tolerance is relative to its positive mass."""
    dv = jax.grad(f)(v)
    direction = _rand(v.shape, 9) * 0.03
    fd = float(f(v + direction) - f(v - direction)) / 2.0
    an = float(jnp.vdot(dv, direction))
    mass = float(jnp.vdot(jnp.abs(dv), jnp.abs(direction)))
    assert abs(an - fd) < 2e-3 * mass, (an, fd, mass)


def _bhtd(tq=128, tk=128):
    return (_rand((1, 1, tq, 64), 0), _rand((1, 1, tk, 64), 1),
            _rand((1, 1, tk, 64), 2))


def _bthd(b=2, h=1, t=128):
    return (_rand((b, t, h, 64), 0), _rand((b, t, h, 64), 1),
            _rand((b, t, h, 64), 2))


def test_dropout_deterministic_and_normalized():
    q, k, v = _bhtd()
    seed = jnp.asarray(42, jnp.int32)
    o1 = fa.flash_attention(q, k, v, seed=seed, p_drop=0.3,
                            q_block=128, k_block=128)
    o2 = fa.flash_attention(q, k, v, seed=seed, p_drop=0.3,
                            q_block=128, k_block=128)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    # Expectation of dropped attention == undropped attention; with 128
    # keys the row means should be close.
    ref = fa._reference_attention(q, k, v, None, 1.0 / np.sqrt(64))
    assert np.abs(np.asarray(o1) - np.asarray(ref)).mean() < 0.15


def test_dropout_grad_v_is_exact_linear():
    q, k, v = _bhtd()
    seed = jnp.asarray(7, jnp.int32)
    _assert_linear_in_v(
        lambda v: jnp.sum(fa.flash_attention(
            q, k, v, seed=seed, p_drop=0.4, q_block=128, k_block=128)), v)


def test_head_groups_draw_their_own_dropout_masks():
    """Heads on the grid (hb < h): the head group joins the seed of a
    score block's mask. Every head is fed the same q, k, v, so two heads
    differ by their masks alone; the dk/dv kernel regenerates the
    forward's mask (out is linear in v) and so does the dq kernel (a
    directional difference in q, at a fixed mask)."""
    h, t, dh = 16, 1024, 128
    assert fa._pick_tile(h, t, t, None, None, dh) == (1, 512, 512)
    q, k, v = (jnp.broadcast_to(_rand((1, 1, t, dh), i), (1, h, t, dh))
               for i in range(3))
    seed = jnp.asarray(17, jnp.int32)

    def attend(q, k, v, p_drop=0.3):
        return fa.flash_attention(q, k, v, seed=seed, p_drop=p_drop)

    plain = np.asarray(attend(q, k, v, 0.0))
    np.testing.assert_array_equal(plain[0, 0], plain[0, 1])
    out = np.asarray(attend(q, k, v))
    np.testing.assert_array_equal(out, np.asarray(attend(q, k, v)))
    for other in range(1, h):
        assert np.abs(out[0, 0] - out[0, other]).mean() > 1e-3, other
    assert np.abs(out - plain).mean() < 0.15
    w = _rand((1, h, t, dh), 5)
    _assert_linear_in_v(lambda v: jnp.sum(attend(q, k, v) * w), v)
    # dq: the loss is smooth in q at a fixed mask, so the analytic
    # directional derivative meets the central difference
    f = lambda q: jnp.sum(attend(q, k, v) * w)
    direction = _rand(q.shape, 9) * 0.01
    an = float(jnp.vdot(jax.grad(f)(q), direction))
    fd = float(f(q + direction) - f(q - direction)) / 2.0
    mass = float(jnp.vdot(jnp.abs(jax.grad(f)(q)), jnp.abs(direction)))
    assert abs(an - fd) < 2e-2 * mass, (an, fd, mass)


def test_bthd_dropout_deterministic():
    q, k, v = _bthd()
    seed = jnp.asarray(13, jnp.int32)
    o1, _ = fa.flash_attention_bthd_fwd(q, k, v, seed=seed, p_drop=0.3)
    o2, _ = fa.flash_attention_bthd_fwd(q, k, v, seed=seed, p_drop=0.3)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    ref = fa._reference_attention_bthd(q, k, v, None, 1.0 / np.sqrt(64))
    assert np.abs(np.asarray(o1) - np.asarray(ref)).mean() < 0.15


def test_bthd_dropout_grad_v_linear():
    q, k, v = _bthd()
    seed = jnp.asarray(5, jnp.int32)

    _assert_linear_in_v(
        lambda v: jnp.sum(fa.flash_attention_bthd_with_lse(
            q, k, v, None, seed, None, 0.4)[0]), v)


def test_dropout_masks_do_not_depend_on_the_batch_split():
    """A batch-sharded caller hands each shard its first GLOBAL row with
    the seed ([seed, row0], ops/attention_ops._on_mesh): two half-batch
    calls must reproduce the full-batch masks."""
    q, k, v = _bthd(b=4)
    full, _ = fa.flash_attention_bthd_fwd(
        q, k, v, seed=jnp.asarray(21, jnp.int32), p_drop=0.3)
    halves = [
        fa.flash_attention_bthd_fwd(
            q[r:r + 2], k[r:r + 2], v[r:r + 2],
            seed=jnp.asarray([21, r], jnp.int32), p_drop=0.3)[0]
        for r in (0, 2)
    ]
    np.testing.assert_array_equal(
        np.asarray(full), np.asarray(jnp.concatenate(halves, axis=0)))
