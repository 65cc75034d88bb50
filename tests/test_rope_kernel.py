"""The rotary embedding's kernels (paddle_tpu/parallel/rope.py:
``rope.fwd`` / ``rope.bwd``) through the Pallas interpreter on the CPU,
as tests/test_pair_sum_kernel.py runs its kernel: against
``ops/attention_ops._rotate`` and its ``jax.vjp`` on the same bf16
values at heads of 128 and 256, grouped heads (28 / 4), both input
layouts and several blocks; forward then backward returns the input;
what ``rope_tile`` takes and refuses; the op under ``layout="bthd"``
against transpose-then-rotate, with and without the kernel; the rows of
``pt_rope_dispatch_total``; and SmallThinker's and OLMoE's tiny Programs
under AMP at heads of 128, kernel against the XLA form. The op with the
heads' gains (``QScale``, ``KScale``: the per-head RMSNorm in the same
pass) against rms_norm, rms_norm and rotary_embedding as three ops, the
shapes ``rope_tile`` refuses through the XLA form, and a call without
gains as the same ``pallas_call`` it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.models import olmoe, smallthinker
from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.ops import nn_ops
from paddle_tpu.parallel import rope

BF16 = jnp.bfloat16
THETA = 1.5e6


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(rope, "_INTERPRET", True)


@pytest.fixture
def calls(monkeypatch):
    """(kernel's name, token-major?) of every call of the kernels, in
    order."""
    made = []
    for fn, name in ((rope.rope_fwd, "rope.fwd"), (rope.rope_bwd, "rope.bwd")):
        def spy(*a, _fn=fn, _name=name, **kw):
            made.append((_name, bool(kw.get("tokens", False))))
            return _fn(*a, **kw)
        monkeypatch.setattr(rope, fn.__name__, spy)
    return made


def values(b, t, h, hk, dh, seed=0):
    """Token-major bf16 q [b, t, h, dh] and k [b, t, hk, dh]."""
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, h, dh), BF16),
            jnp.asarray(r.randn(b, t, hk, dh), BF16))


def heads_first(z):
    return jnp.swapaxes(z, 1, 2)


def to_bf16_rounding(got, want):
    """``got`` (bf16) is the float32 ``want`` rounded once, give or take
    the last float32 bit of a product's sum (an FMA or not)."""
    assert got.dtype == BF16 and got.shape == want.shape
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_array_less(
        np.abs(got - want), np.abs(want) * 2.0 ** -7 + 1e-6)


def rotate32(x):
    """``_rotate`` of head-major bf16 values, left in float32."""
    return ao._rotate(x.astype(jnp.float32), THETA)


# (b, t, q heads, k heads, dh, (rows, heads of q a step))
CASES = {
    "grouped_28_4": (1, 64, 28, 4, 128, (32, 28)),
    "grouped_a_k_head_a_step": (1, 64, 28, 4, 128, (32, 4)),
    "a_head_a_step": (2, 96, 4, 4, 128, (32, 1)),
    "two_passes_a_block": (2, 128, 4, 2, 128, (64, 4)),
    "dh256": (1, 64, 4, 2, 256, (32, 4)),
    "dh256_one_block": (2, 32, 2, 1, 256, (32, 2)),
}


@pytest.mark.parametrize("tokens", [True, False],
                         ids=["token_major", "head_major"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_forward_kernel_is_rotate(case, tokens, interpreter):
    b, t, h, hk, dh, tile = CASES[case]
    q, k = values(b, t, h, hk, dh)
    qh, kh = heads_first(q), heads_first(k)
    got = rope.rope_fwd(*((q, k) if tokens else (qh, kh)), THETA, tile,
                        tokens=tokens)
    for g, x in zip(got, (qh, kh)):
        to_bf16_rounding(g, rotate32(x))


@pytest.mark.parametrize("tokens", [True, False],
                         ids=["token_major", "head_major"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_backward_kernel_is_rotate_s_vjp(case, tokens, interpreter):
    b, t, h, hk, dh, tile = CASES[case]
    q, k = values(b, t, h, hk, dh)
    gq, gk = (heads_first(z) for z in values(b, t, h, hk, dh, seed=1))
    got = rope.rope_bwd(gq, gk, THETA, tile, tokens=tokens)
    for d, x, g in zip(got, (q, k), (gq, gk)):
        want = jax.vjp(rotate32, heads_first(x).astype(jnp.float32))[1](
            g.astype(jnp.float32))[0]
        to_bf16_rounding(heads_first(d) if tokens else d, want)
        assert d.shape == (x.shape if tokens else heads_first(x).shape)


@pytest.mark.parametrize("case", list(CASES))
def test_forward_then_backward_returns_the_input(case, interpreter):
    """A rotation's transpose is its inverse: two bf16 roundings, each
    of a value no longer than the pair it belongs to."""
    b, t, h, hk, dh, tile = CASES[case]
    q, k = values(b, t, h, hk, dh, seed=2)
    back = rope.rope_bwd(*rope.rope_fwd(q, k, THETA, tile, tokens=True),
                         THETA, tile, tokens=True)
    for got, x in zip(back, (q, k)):
        got, x = (np.asarray(z.astype(jnp.float32)) for z in (got, x))
        pair = np.hypot(x, np.roll(x, dh // 2, axis=-1))
        np.testing.assert_array_less(np.abs(got - x),
                                     pair * 2.0 ** -7 + 1e-6)


def test_position_zero_is_not_turned_and_a_turn_keeps_a_pair_s_length(
        interpreter):
    q, k = values(1, 64, 4, 2, 128, seed=3)
    qo, _ = rope.rope_fwd(q, k, THETA, (32, 4), tokens=True)
    np.testing.assert_array_equal(qo[:, :, 0], heads_first(q)[:, :, 0])
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(qo.astype(jnp.float32)), axis=-1),
        np.linalg.norm(np.asarray(q.astype(jnp.float32)), axis=-1).swapaxes(
            1, 2), rtol=1e-2)


TAKEN = dict(b=1, t=16384, h=28, dh=128, rotary_dim=None, interleaved=False,
             dtype=BF16, hk=4, backend="tpu", on_mesh=False)


@pytest.mark.parametrize("call,want", [
    ({}, (256, 28)),                                   # smallthinker
    (dict(b=2, t=4096, h=16, hk=16), (256, 16)),       # olmoe
    (dict(rotary_dim=128), (256, 28)),                 # the whole head, said
    (dict(t=4096 + 128), (128, 28)),
    (dict(t=96), (32, 28)),
    (dict(dh=256, h=16, hk=2), (256, 16)),
    (dict(dtype=jnp.float32), None),
    (dict(on_mesh=True), None),
    (dict(backend="cpu"), None),
    (dict(rotary_dim=64), (256, 28)),    # a part of a head one vreg wide
    (dict(t=8192, h=48, hk=8, rotary_dim=64), (256, 48)),   # laguna, full
    (dict(rotary_dim=63), None),                       # not whole pairs
    (dict(rotary_dim=64, interleaved=True), None),
    (dict(dh=256, rotary_dim=64), None),               # qwen3next
    (dict(dh=64, interleaved=True, hk=1), None),       # joyai
    (dict(interleaved=True), None),
    (dict(dh=64), None),
    (dict(dh=192), None),
    (dict(t=40), None),                                # off every block
    (dict(t=0), None),
    (dict(h=1024, hk=1024), None),                     # over the VMEM cap
    # with the heads' gains: sdar's call, the backward's third operand
    # counted (which takes a block of rows off a call of 128 heads)
    (dict(t=8192, h=32, hk=4, periods=2, norm=True), (256, 32)),
    (dict(h=96, hk=32), (256, 96)),
    (dict(h=96, hk=32, norm=True), (128, 96)),
    (dict(dh=64, norm=True), None),
    (dict(dh=256, rotary_dim=64, norm=True), None),
    (dict(dtype=jnp.float32, norm=True), None),
], ids=lambda v: "_".join(f"{k}{getattr(x, '__name__', x)}"
                          for k, x in v.items()) or "smallthinker"
   if isinstance(v, dict) else None)
def test_rope_tile_follows_the_shape_the_dtype_the_backend_and_the_mesh(
        call, want):
    kw = {**TAKEN, **call}
    assert rope.rope_tile(kw.pop("b"), kw.pop("t"), kw.pop("h"),
                          kw.pop("dh"), kw.pop("rotary_dim"),
                          kw.pop("interleaved"), kw.pop("dtype"),
                          **kw) == want


def test_no_tile_without_a_tpu_or_the_interpreter():
    assert rope.rope_tile(1, 16384, 28, 128, None, False, BF16, hk=4) is None


# --- the op ---------------------------------------------------------------


def op_pair(q, k, gq, gk, layout, **attrs):
    """(QOut, KOut, GRAD::Q, GRAD::K) of the op rules on Q and K."""
    attrs = {"theta": THETA, **attrs}
    if layout != "bhtd":
        attrs["layout"] = layout
    ins = {"Q": [q], "K": [k]}
    out = ao._rotary_embedding(ins, attrs)
    grad = ao._rotary_embedding_grad(
        {**ins, **out, "GRAD::QOut": [gq], "GRAD::KOut": [gk]},
        {**attrs, "fwd_input_slots": ["Q", "K"],
         "fwd_output_slots": ["QOut", "KOut"]})
    return (out["QOut"][0], out["KOut"][0], grad["GRAD::Q"][0],
            grad["GRAD::K"][0])


@pytest.mark.parametrize("t", [64, 40], ids=["on_the_row_block", "off_it"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_op_token_major_is_transpose_then_rotate(dtype, t, interpreter,
                                                     calls):
    """``layout="bthd"``: the head-major op on transposed inputs, its
    gradients transposed back; through the kernel where ``rope_tile``
    takes the call (bf16, t on a block of rows), XLA's ops elsewhere."""
    q, k = (z.astype(dtype) for z in values(2, t, 4, 2, 128, seed=4))
    gq, gk = (heads_first(z).astype(dtype)
              for z in values(2, t, 4, 2, 128, seed=5))
    got = op_pair(q, k, gq, gk, "bthd")
    kernel = dtype == "bfloat16" and t == 64
    assert calls == [("rope.fwd", True), ("rope.bwd", True)] * kernel
    with pytest.MonkeyPatch.context() as m:
        m.setattr(rope, "rope_tile", lambda *a, **kw: None)
        want = op_pair(heads_first(q), heads_first(k), gq, gk, "bhtd")
    assert len(calls) == 2 * kernel
    for g, w, back in zip(got, want, (False, False, True, True)):
        w = heads_first(w) if back else w
        assert g.dtype == w.dtype and g.shape == w.shape
        if kernel:
            to_bf16_rounding(g, w.astype(jnp.float32))
        else:
            np.testing.assert_array_equal(g, w)


def test_the_op_head_major_takes_the_kernel_too(interpreter, calls):
    q, k = (heads_first(z) for z in values(1, 64, 4, 4, 128, seed=6))
    got = op_pair(q, k, q, k, "bhtd")
    assert calls == [("rope.fwd", False), ("rope.bwd", False)]
    for g, x in zip(got[:2], (q, k)):
        to_bf16_rounding(g, rotate32(x))


def test_a_cotangent_the_program_does_not_give_is_zeros(interpreter):
    """Only q's result reaches the loss: GRAD::K is zeros, GRAD::Q the
    kernel's."""
    q, k = values(1, 64, 4, 2, 128, seed=7)
    g = heads_first(q)
    ins = {"Q": [q], "K": [k], "GRAD::QOut": [g], "GRAD::KOut": [None]}
    grad = ao._rotary_embedding_grad(ins, {"theta": THETA, "layout": "bthd"})
    assert not np.asarray(grad["GRAD::K"][0].astype(jnp.float32)).any()
    want = jax.vjp(rotate32, g.astype(jnp.float32))[1](
        g.astype(jnp.float32))[0]
    to_bf16_rounding(heads_first(grad["GRAD::Q"][0]), want)


# --- the op with the heads' gains -------------------------------------------

EPS = 1e-6


def gains(dh, seed):
    """q's and k's gain [dh] float32, normal(2.0, 0.2) as a run of
    sdar-train-s4096 lays them."""
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(2.0 + 0.2 * r.randn(dh), jnp.float32)
                 for _ in "qk")


def fused_op(q, k, sq, sk, gq, gk, **attrs):
    """(QOut, KOut, GRAD::Q, GRAD::K, GRAD::QScale, GRAD::KScale) of the
    op with QScale and KScale."""
    attrs = {"theta": THETA, "norm_epsilon": EPS, **attrs}
    ins = {"Q": [q], "K": [k], "QScale": [sq], "KScale": [sk]}
    out = ao._rotary_embedding(ins, attrs)
    grad = ao._rotary_embedding_grad(
        {**ins, **out, "GRAD::QOut": [gq], "GRAD::KOut": [gk]},
        {**attrs, "fwd_input_slots": list(ins),
         "fwd_output_slots": ["QOut", "KOut"]})
    return (out["QOut"][0], out["KOut"][0],
            *(grad[f"GRAD::{slot}"][0] for slot in ins))


def three_ops(q, k, sq, sk, gq, gk, **attrs):
    """The same of rms_norm, rms_norm and rotary_embedding without
    gains, each op's rule and its grad op's in the Program's order."""
    def norm(x, scale):
        return nn_ops._rms_norm({"X": [x], "Scale": [scale]},
                                {"epsilon": EPS})["Y"][0]

    (qn, q_vjp), (kn, k_vjp) = jax.vjp(norm, q, sq), jax.vjp(norm, k, sk)
    qo, ko, dqn, dkn = op_pair(qn, kn, gq, gk, attrs.pop("layout", "bhtd"),
                               **attrs)
    (dq, dsq), (dk, dsk) = q_vjp(dqn), k_vjp(dkn)
    return qo, ko, dq, dk, dsq, dsk


def as_the_three_ops(got, want, unequal=1e-4):
    """The values to a bf16 rounding (a float32 lane sum in another
    order moves the statistic's last bit, and with it a value that lay
    on a tie), all but ``unequal`` of them equal; the gains' gradients,
    float32 sums over every row and head, to 2e-4 of the largest (a
    cotangent that rounds the other way is 2^-8 of one term)."""
    for g, w in zip(got[:4], want[:4]):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = (np.asarray(z.astype(jnp.float32)) for z in (g, w))
        np.testing.assert_array_less(np.abs(g - w),
                                     np.abs(w) * 2.0 ** -7 + 1e-6)
        assert (g != w).mean() <= unequal
    for g, w in zip(got[4:], want[4:]):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2e-4 * float(np.abs(w).max()))


@pytest.mark.parametrize("periods", [1, 2])
def test_the_op_with_gains_is_the_three_ops(periods, interpreter, calls):
    """[1, 512, 8 + 2, 128] token-major, gains of normal(2.0, 0.2):
    one call of each kernel, and what rms_norm, rms_norm and the rotary
    op give as three."""
    q, k = values(1, 512, 8, 2, 128, seed=8)
    q = q * 3                  # (a projection's q is not of unit length)
    gq, gk = (heads_first(z) for z in values(1, 512, 8, 2, 128, seed=9))
    attrs = {"layout": "bthd", **({"periods": 2} if periods == 2 else {})}
    got = fused_op(q, k, *gains(128, 10), gq, gk, **attrs)
    assert calls == [("rope.fwd", True), ("rope.bwd", True)]
    want = three_ops(q, k, *gains(128, 10), gq, gk, **attrs)
    assert len(calls) == 4
    as_the_three_ops(got, want)
    assert got[2].shape == q.shape and got[3].shape == k.shape


def test_the_op_with_gains_head_major_and_a_block_of_heads(interpreter):
    """Head-major q and k, and a tile that takes q's heads in two blocks
    (k's in the first: its gain's tile of partial sums is written once a
    block of rows)."""
    q, k = (heads_first(z) for z in values(2, 64, 4, 2, 128, seed=11))
    gq, gk = (heads_first(z) for z in values(2, 64, 4, 2, 128, seed=12))
    sq, sk = gains(128, 13)
    want = three_ops(q, k, sq, sk, gq, gk)
    as_the_three_ops(fused_op(q, k, sq, sk, gq, gk), want, unequal=1e-3)
    qo, ko = rope.rope_fwd(q, k, THETA, (32, 2), gains=(sq, sk), eps=EPS)
    got = rope.rope_bwd(gq, gk, THETA, (32, 2), gains=(sq, sk), eps=EPS,
                        x=(q, k))
    as_the_three_ops((qo, ko, *got), want, unequal=1e-3)


def test_the_op_with_gains_and_a_cotangent_for_q_only(interpreter):
    q, k = values(1, 64, 4, 2, 128, seed=14)
    gq = heads_first(values(1, 64, 4, 2, 128, seed=15)[0])
    sq, sk = gains(128, 16)
    got = fused_op(q, k, sq, sk, gq, None, layout="bthd")
    want = three_ops(q, k, sq, sk, gq, None, layout="bthd")
    as_the_three_ops(got, want, unequal=1e-3)
    assert not np.asarray(got[3].astype(jnp.float32)).any()
    assert not np.asarray(got[5]).any() and np.asarray(got[4]).any()


@pytest.mark.parametrize("dh,dtype,attrs", [
    (64, "bfloat16", {}),                          # lfm2moe's heads
    (256, "bfloat16", {"rotary_dim": 64}),         # qwen3next's part
    (128, "float32", {}),                          # the reference's path
], ids=["heads_of_64", "64_of_256", "float32"])
def test_a_refused_call_with_gains_is_the_three_ops_as_xla_s(
        dh, dtype, attrs, interpreter, calls):
    """Where ``rope_tile`` gives no tile the op is rms_norm's lines in
    front of ``_rotary_xla`` and its grad op that composition's vjp."""
    q, k = (z.astype(dtype) for z in values(1, 64, 4, 2, dh, seed=17))
    gq, gk = (heads_first(z).astype(dtype)
              for z in values(1, 64, 4, 2, dh, seed=18))
    sq, sk = gains(dh, 19)
    got = fused_op(q, k, sq, sk, gq, gk, layout="bthd", **attrs)
    want = three_ops(q, k, sq, sk, gq, gk, layout="bthd", **attrs)
    assert calls == []
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)),
            np.asarray(w.astype(jnp.float32)), rtol=1e-5, atol=1e-6)


def test_one_gain_alone_is_refused():
    q, k = values(1, 64, 4, 2, 128)
    with pytest.raises(ValueError, match="QScale and KScale"):
        ao._rotary_embedding({"Q": [q], "K": [k], "QScale": [gains(128, 0)[0]]},
                             {"theta": THETA, "norm_epsilon": EPS})


def pallas_calls(fn, *args):
    """(operands, results) of every ``pallas_call`` in fn's jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((len(eqn.invars), len(eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_a_call_without_gains_is_the_call_it_was(interpreter):
    """q, k and the two tables in, q and k out: the norm is a static
    part of the kernel's body that a call without gains does not trace,
    and its operands ride only in a call that brings them."""
    q, k = values(1, 64, 4, 2, 128, seed=20)
    g = tuple(heads_first(z) for z in (q, k))
    sq, sk = gains(128, 21)
    tile = (32, 4)
    assert pallas_calls(lambda a, b: rope.rope_fwd(
        a, b, THETA, tile, tokens=True), q, k) == [(4, 2)]
    assert pallas_calls(lambda a, b: rope.rope_bwd(
        a, b, THETA, tile, tokens=True), *g) == [(4, 2)]
    assert pallas_calls(lambda a, b: rope.rope_fwd(
        a, b, THETA, tile, tokens=True, gains=(sq, sk), eps=EPS),
        q, k) == [(6, 2)]
    assert pallas_calls(lambda a, b: rope.rope_bwd(
        a, b, THETA, tile, tokens=True, gains=(sq, sk), eps=EPS, x=(q, k)),
        *g) == [(8, 4)]
    # said or not, a call without gains is one jitted function
    plain = rope.rope_fwd(q, k, THETA, tile, tokens=True)
    said = rope.rope_fwd(q, k, THETA, tile, tokens=True, gains=None,
                         eps=EPS)
    for a, b in zip(plain, said):
        np.testing.assert_array_equal(a, b)


def rotary_program(norm):
    """A Program under AMP that is a projection to q | k, one rotary op
    on them [b, 64, 4 + 2, 128] token-major, with or without the heads'
    gains, and its backward."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[64, 16])
        qk = layers.fc(x, 6 * 128, num_flatten_dims=2, bias_attr=False,
                       param_attr=fluid.ParamAttr(name="w"))
        q, k = layers.split(qk, [4 * 128, 2 * 128], dim=-1)
        qo, ko = layers.rotary_embedding(
            layers.reshape(q, [0, 0, 4, 128]),
            layers.reshape(k, [0, 0, 2, 128]), theta=THETA, layout="bthd",
            norm_epsilon=EPS,
            norm_param_attrs=["qnorm.scale", "knorm.scale"] if norm else None)
        loss = layers.elementwise_add(layers.reduce_sum(qo),
                                      layers.reduce_sum(ko))
        grads = append_backward(loss)
    main._amp = True
    return main, startup, loss, grads


@pytest.mark.parametrize("norm", [False, True], ids=["plain", "gains"])
def test_the_rows_of_a_call_with_gains_carry_norm_head(norm, interpreter):
    """One row a lowered call: ``norm="head"`` on the rows of a call
    that brings the gains, no such label on the others (the parent's
    rows)."""
    main, startup, loss, grads = rotary_program(norm)
    assert [p.name for p, _ in grads] == [
        "w", *["qnorm.scale", "knorm.scale"] * norm]
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    x = np.random.RandomState(22).randn(1, 64, 16).astype(np.float32)
    flags.set_flags({"telemetry": True})
    try:
        before = rope_rows()
        exe.run(main, feed={"x": x}, scope=scope,
                fetch_list=[loss, *(g for _, g in grads)])
        rows = rope_rows(before)
    finally:
        flags.set_flags({"telemetry": False})
    want = {"impl": "kernel", "layout": "bthd", "dh": "128",
            "scaling": "none", **({"norm": "head"} if norm else {})}
    assert {frozenset(dict(k).items()): n for k, n in rows.items()} == {
        frozenset({**want, "pass": d}.items()): 1 for d in ("fwd", "bwd")}


def test_the_layer_names_the_layout_and_refuses_another():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = layers.data("q", shape=[2, 16, 4, 8], append_batch_size=False)
        k = layers.data("k", shape=[2, 16, 2, 8], append_batch_size=False)
        qo, ko = layers.rotary_embedding(q, k, layout="bthd")
        assert tuple(qo.shape) == (2, 4, 16, 8)
        assert tuple(ko.shape) == (2, 2, 16, 8)
        with pytest.raises(ValueError, match="layout"):
            layers.rotary_embedding(q, k, layout="tbhd")
    op = main.global_block().ops[-1]
    assert op.type == "rotary_embedding" and op.attrs["layout"] == "bthd"


# --- Programs ---------------------------------------------------------------


def rope_rows(before=()):
    """{labels: calls} of pt_rope_dispatch_total, the rows that moved
    since ``before`` (an earlier reading) alone."""
    rows = {tuple(sorted(r["labels"].items())): int(r["value"])
            for r in monitor.snapshot().get(
                "pt_rope_dispatch_total", {}).get("values", [])}
    before = dict(before)
    return {k: v - before.get(k, 0) for k, v in rows.items()
            if v != before.get(k, 0)}


SMALLTHINKER = dict(
    vocab_size=50, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=128,
    rope_theta=1.5e6, rms_norm_eps=1e-6, sliding_window_size=5,
    sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    moe_num_primary_experts=4, moe_num_active_primary_experts=2,
    moe_ffn_hidden_size=16, norm_topk_prob=True)
OLMOE = dict(vocab_size=50, hidden_size=256, num_hidden_layers=1,
             num_attention_heads=2, intermediate_size=16, num_experts=4,
             num_experts_per_tok=2, rope_theta=10000.0, rms_norm_eps=1e-5,
             norm_topk_prob=False, router_aux_loss_coef=0.01,
             router_z_loss_coef=0.001)
MODELS = {"smallthinker": (smallthinker, smallthinker.SmallThinkerConfig,
                           SMALLTHINKER, 3),
          "olmoe": (olmoe, olmoe.OlmoeConfig, OLMOE, 1)}


def loss_and_gradients(model, cfg, seq):
    """(loss, {parameter: gradient}) of a model's Program under AMP."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        built = model.build(cfg)
        grads = append_backward(built["loss"])
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    out = exe.run(main, feed=model.make_batch(cfg, 2, seq, seed=1),
                  scope=scope, fetch_list=[built["loss"],
                                           *(g for _, g in grads)])
    return float(out[0]), {p.name: np.asarray(g, np.float32)
                           for (p, _), g in zip(grads, out[1:])}


@pytest.mark.parametrize("name", list(MODELS))
def test_a_model_s_program_with_the_kernel_is_the_program_without(
        name, interpreter, monkeypatch):
    """The tiny Program at heads of 128 under AMP, its rotating layers
    through ``rope.fwd`` / ``rope.bwd`` (one row a lowered call in
    ``pt_rope_dispatch_total``), against the same Program with
    ``rope_tile`` giving no tile (XLA's transpose and ``_rotate``: the
    parent's lowering): the loss and every parameter's gradient."""
    model, config, sizes, rotating = MODELS[name]
    cfg = config(**sizes)
    flags.set_flags({"telemetry": True})
    try:
        before = rope_rows()
        loss, grads = loss_and_gradients(model, cfg, 32)
        by_impl = {}
        for labels, n in rope_rows(before).items():
            labels = dict(labels)
            assert labels["layout"] == "bthd" and labels["dh"] == "128"
            key = labels["impl"], labels["pass"]
            by_impl[key] = by_impl.get(key, 0) + n
        assert by_impl == {("kernel", "fwd"): rotating,
                           ("kernel", "bwd"): rotating}
        monkeypatch.setattr(rope, "rope_tile", lambda *a, **kw: None)
        before = rope_rows()
        want_loss, want = loss_and_gradients(model, cfg, 32)
        assert {dict(k)["impl"] for k in rope_rows(before)} == {"xla"}
    finally:
        flags.set_flags({"telemetry": False})
    np.testing.assert_allclose(loss, want_loss, rtol=2e-3)
    assert grads.keys() == want.keys()
    for key in want:
        scale = float(np.abs(want[key]).max()) or 1.0
        np.testing.assert_allclose(grads[key] / scale, want[key] / scale,
                                   atol=3e-2, err_msg=key)
