"""``tgmm_adam`` (paddle_tpu/parallel/grouped_matmul.py): the experts'
weight-gradient kernel with Adam's step on its accumulator, on the CPU
through the Pallas interpreter, against ``tgmm`` over the same operands
in float32 followed by the ``adam`` / ``adamw`` op itself
(ops/optimizer_ops.py); which calls ``grouped_matmul_grads`` gives the
one call and which the two passes; and the tile of the matrix the call
takes. The chip's compile of the cells' shapes is
tests/test_attention_compiles_for_v5e.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags, monitor
from paddle_tpu.core import interp
from paddle_tpu.ops import optimizer_ops as opt
from paddle_tpu.parallel import grouped_matmul as gm

BF, F32 = jnp.bfloat16, jnp.float32
ATTRS = {"beta1": 0.9, "beta2": 0.99, "epsilon": 1e-6, "weight_decay": 0.1}
# a third step: the beta powers are no longer 1
POWS = (jnp.full((1,), 0.81, F32), jnp.full((1,), 0.9801, F32))
LR = jnp.full((1,), 0.02, F32)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gm, "_INTERPRET", True)


# name: (m, k, n, group sizes, (tm, tk, tn)); the sizes sum to m unless
# the case is a held share
CASES = {
    "even_groups": (512, 128, 128, [128, 128, 128, 128], (128, 128, 128)),
    "an_empty_group": (512, 128, 128, [300, 0, 12, 200], (128, 128, 128)),
    "first_and_last_empty": (512, 128, 128, [0, 255, 257, 0],
                             (128, 128, 128)),
    "tiles_straddle_two_groups": (512, 128, 128, [37, 301, 5, 169],
                                  (256, 128, 128)),
    "a_held_share": (1024, 128, 128, [130, 60, 0, 150], (128, 128, 128)),
    "a_held_share_without_rows": (512, 128, 128, [0, 0], (128, 128, 128)),
    "k_of_three_tiles": (512, 384, 128, [64, 3, 0, 190, 61, 194],
                         (128, 128, 128)),
    "n_of_two_tiles_k_whole": (512, 256, 256, [1, 254, 129, 128],
                               (128, 256, 128)),
    "a_contraction_off_the_lanes": (256, 232, 128, [200, 56],
                                    (128, 232, 128)),
}


def operands(m, k, n, sizes, seed=0):
    r = np.random.RandomState(seed)
    e, live = len(sizes), int(np.sum(sizes))
    lhs, g = r.randn(m, k), r.randn(m, n)
    # what lies behind the last group's tile is never read
    behind = -(-max(live, 1) // 128) * 128
    g[behind:] = np.nan
    state = (r.randn(e, k, n) * 0.1, r.randn(e, k, n) * 0.01,
             np.abs(r.randn(e, k, n)) * 0.01)
    return (jnp.asarray(lhs, BF), jnp.asarray(g, BF),
            jnp.asarray(sizes, jnp.int32),
            tuple(jnp.asarray(x, F32) for x in state))


def the_op(op_type, state, dw):
    """(ParamOut, Moment1Out, Moment2Out) of the registered op."""
    p, m1, m2 = state
    outs = {"adam": opt._adam, "adamw": opt._adamw}[op_type]({
        "Param": [p], "Grad": [dw], "Moment1": [m1], "Moment2": [m2],
        "Beta1Pow": [POWS[0]], "Beta2Pow": [POWS[1]],
        "LearningRate": [LR]}, ATTRS)
    return [outs[k][0] for k in ("ParamOut", "Moment1Out", "Moment2Out")]


def the_step(op_type, state):
    lr = LR.reshape(())
    return gm.AdamStep(
        state, opt.adam_lr_t(lr, POWS[0] * ATTRS["beta1"],
                             POWS[1] * ATTRS["beta2"]),
        lr * ATTRS["weight_decay"] if op_type == "adamw" else None,
        ATTRS["beta1"], ATTRS["beta2"], ATTRS["epsilon"])


def fused(op_type, lhs, g, gs, tile, state):
    return gm.tgmm_adam(lhs, g, gs, tile, the_step(op_type, state))


@pytest.mark.parametrize("op_type", ["adam", "adamw"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_fused_step_is_the_op_on_the_float32_gradient(case, op_type,
                                                          interpreted):
    m, k, n, sizes, tile = CASES[case]
    lhs, g, gs, state = operands(m, k, n, sizes)
    got = fused(op_type, lhs, g, gs, tile, state)
    live = jnp.arange(m)[:, None] < jnp.sum(gs)
    dw = gm.tgmm(lhs.astype(F32), jnp.where(live, g, 0).astype(F32), gs,
                 tile)
    want = the_op(op_type, state, dw)
    for name, a, b in zip(("weight", "moment1", "moment2"), got, want):
        assert a.dtype == F32 and a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7, err_msg=name)
    for e, rows in enumerate(sizes):
        if rows == 0:   # no gradient: the moments decay, the weight moves
            np.testing.assert_allclose(got[1][e],
                                       ATTRS["beta1"] * state[1][e],
                                       rtol=1e-6)
            assert np.mean(np.asarray(got[0][e] != state[0][e])) > 0.9


def test_the_three_results_alias_the_state(interpreted):
    m, k, n, sizes, tile = CASES["even_groups"]
    lhs, g, gs, state = operands(m, k, n, sizes)
    jaxpr = jax.make_jaxpr(
        lambda *a: fused("adam", *a[:3], tile, a[3:]))(lhs, g, gs, *state)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    # operands: the four of the scalar prefetch, lhs, g, the scalars,
    # then the weight and its two moments
    assert tuple(call.params["input_output_aliases"]) == (
        (7, 0), (8, 1), (9, 2))
    assert [v.aval.shape for v in call.invars[7:]] == [state[0].shape] * 3
    assert call.params["name"] == "moe.tgmm.bwd_dw_adam"   # the moe family


@pytest.mark.parametrize("tile,k,n,e,rows,want", [
    # olmoe-train-s4096's two matrices: the widest pair under the cap
    # that reads the rows least often
    ((256, 2048, 1024), 2048, 1024, 64, 65536, (256, 1024, 1024)),
    ((256, 1024, 2048), 1024, 2048, 64, 65536, (256, 1024, 1024)),
    # a held share's narrow experts, whole (Qwen3-Next, Laguna)
    ((128, 2048, 512), 2048, 512, 32, 5120, (128, 2048, 512)),
    ((128, 512, 2048), 512, 2048, 16, 4096, (128, 512, 2048)),
    # 768 wide (SDAR, JoyAI) and LFM2's 1536: two and three tiles
    ((128, 2048, 768), 2048, 768, 16, 8192, (128, 1024, 768)),
    ((128, 1536, 2048), 1536, 2048, 8, 4096, (128, 512, 2048)),
    # Nemotron's 1856: whole as a contraction; as a width it is off the
    # lanes and the kernel's own copies cannot slice it
    ((128, 1856, 896), 1856, 2688, 8, 1536, (128, 1856, 384)),
    ((128, 2688, 640), 2688, 1856, 8, 1536, None),
    # SmallThinker's share: five tiles of 512 x 768 read g four times
    # more, 75 MB for the 63 MB of gradient the form saves; with an
    # eighth of the rows it would pay
    ((256, 2560, 768), 2560, 768, 8, 12288, None),
    ((256, 768, 2560), 768, 2560, 8, 12288, None),
    ((256, 2560, 768), 2560, 768, 8, 1536, (256, 512, 768)),
])
def test_adam_tile_by_shape(tile, k, n, e, rows, want):
    got = gm.adam_tile(tile, k, n, e, rows)
    assert got == want
    if want:
        tm, tk, tn = got
        assert tm == tile[0] and k % tk == 0 and n % tn == 0
        assert gm._adam_vmem_bytes(tm, tk, tn, 2) <= gm._VMEM_CAP_BYTES


def counts_of(fn):
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        out = fn()
        return out, gm.gmm_dispatch_counts()
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()


@pytest.mark.parametrize("op_type", ["adam", "adamw"])
def test_grads_take_the_step_in_the_kernel_where_the_call_has_a_tile(
        op_type, interpreted):
    m, k, n, sizes = 512, 128, 256, [128, 100, 156, 128]
    lhs, g, gs, state = operands(m, k, n, sizes, seed=3)
    rhs = state[0].astype(BF)
    (dx, got), counts = counts_of(lambda: gm.grouped_matmul_grads(
        lhs, rhs, gs, g, adam=the_step(op_type, state)))
    assert counts == {
        "bwd_dx m512 k128 n256 e4 [tm128 tk256 tn128]": 1,
        "bwd_dw_adam m512 k128 n256 e4 [tm128 tk128 tn256]": 1}
    want_dx, dw = gm.grouped_matmul_grads(lhs, rhs, gs, g)
    np.testing.assert_array_equal(dx, want_dx)
    # the two passes round the gradient to bf16 in between
    for a, b in zip(got, the_op(op_type, state, dw)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2 * scale)


@pytest.mark.parametrize("why", ["no_tpu", "a_mesh", "float32_rows",
                                 "bf16_moments", "a_width_off_the_lanes"])
def test_grads_take_the_step_behind_the_gradient_elsewhere(why, monkeypatch):
    """No kernel (the CPU; a mesh, where a chip's partial gradient is
    summed first), operands that are not bf16, state that is not
    float32, a matrix ``adam_tile`` has no tile for: the gradient is
    made as ever and the op's own arithmetic follows it, to the bit,
    counted as ``bwd_dw``."""
    m, k, n, sizes = 512, 128, 256, [128, 100, 156, 128]
    if why == "a_width_off_the_lanes":
        n = 192
    lhs, g, gs, state = operands(m, k, n, sizes, seed=4)
    if why != "no_tpu":
        monkeypatch.setattr(gm, "_INTERPRET", True)
    if why == "a_mesh":
        monkeypatch.setattr(gm, "_under_mesh", lambda: True)
    if why == "float32_rows":
        lhs, g = lhs.astype(F32), g.astype(F32)
    if why == "bf16_moments":
        state = (state[0], state[1].astype(BF), state[2].astype(BF))
    rhs = state[0].astype(lhs.dtype)
    (dx, got), counts = counts_of(lambda: gm.grouped_matmul_grads(
        lhs, rhs, gs, g, adam=the_step("adam", state)))
    assert not any(name.startswith("bwd_dw_adam") for name in counts)
    assert sum(v for name, v in counts.items()
               if name.startswith("bwd_dw ")) == 1
    # with a tile the gradient went to HBM; without, ragged_dot made it
    assert any("[tm128" in name for name in counts) == (
        why in ("bf16_moments", "a_width_off_the_lanes"))
    want_dx, dw = gm.grouped_matmul_grads(lhs, rhs, gs, g)
    np.testing.assert_array_equal(dx, want_dx)
    for a, b in zip(got, the_op("adam", state, dw)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
