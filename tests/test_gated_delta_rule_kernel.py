"""The gated delta rule's Pallas kernels
(paddle_tpu/parallel/gated_delta_rule.py) on the CPU through the Pallas
interpreter, at dk = dv = 128 and a few chunks: against the float32
recurrence and its ``jax.vjp`` (Out and all five gradients), against the
chunked XLA form they replace (``States``), the triangle's inverse alone
against float64, the picker's table, the dispatch counter, and the op
through a Program under AMP. The chip's
run of the cell's shapes is tests/test_gated_delta_rule_tpu.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import paddle_tpu as fluid
from op_test import delta_rule_op
from op_test import delta_rule_recurrence as recurrence
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.parallel import gated_delta_rule as gdr

BF, F32 = jnp.bfloat16, jnp.float32
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gdr, "_INTERPRET", True)


def operands(t, hk, hv, seed=0, dtype=BF, b=1):
    r = np.random.RandomState(seed)
    q, k = (jnp.asarray(r.randn(b, t, hk, 128), dtype) for _ in "qk")
    v, do = (jnp.asarray(r.randn(b, t, hv, 128), dtype) for _ in "vd")
    return (q, k, v, -jnp.asarray(r.rand(b, t, hv) * 0.5, F32),
            jnp.asarray(r.rand(b, t, hv), F32), do)


def through_the_op(q, k, v, g, beta, do, **attrs):
    return delta_rule_op(**attrs)(q, k, v, g, beta, do)


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


# (positions, key heads, value heads): one chunk; several chunks in one
# grid step; a padded last chunk; more chunks than a grid step holds,
# padded to two steps; hk = hv and hv = 2 hk
CASES = [(64, 1, 1), (256, 1, 2), (150, 1, 2), (600, 1, 1), (128, 2, 2),
         (192, 2, 4)]


@pytest.mark.parametrize("t,hk,hv", CASES)
def test_kernels_are_the_recurrence(t, hk, hv, interpreted):
    """bf16 operands, so to bf16's rounding of the chunk's matmuls: the
    chunked XLA form reads 0.003-0.006 of the largest entry here."""
    args = operands(t, hk, hv, seed=t)
    assert gdr.gdn_tile(t, hk, hv, 128, 128, 64, BF) == (
        hv // hk, min(8, -(-t // 64)))
    got, states = through_the_op(*args, chunk=64)
    assert states.shape == (-(-t // 64), 1, hv, 128, 128)
    assert states.dtype == BF and got[0].dtype == BF
    assert got[4].dtype == F32 and got[5].dtype == F32
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 0.012, (name, rel(a, b))


# (positions, key heads, value heads): groups of 1, 2 and 3 value heads a
# key head (an odd last head alone in its pair) over 8 x 64 + 37
# positions (nine chunks: a whole grid step and a second of one chunk and
# seven of padding); a group of 3 under two key heads in a sequence
# shorter than a grid step; two whole grid steps (the state and dS cross
# a block with every chunk live)
BLOCKS = [(549, 1, 1), (549, 1, 2), (549, 1, 3), (200, 2, 6), (1024, 1, 2)]


@pytest.mark.parametrize("t,hk,hv", BLOCKS)
def test_groups_and_blocks_against_both_references(t, hk, hv, interpreted,
                                                   monkeypatch):
    """What a grid step makes once (the pre-pass), the state loop and
    the pass behind it, across group sizes and block boundaries: Out and
    all five gradients against the float32 recurrence AND against the
    chunked XLA form the kernels replace, the states against the
    chunked form's."""
    args = operands(t, hk, hv, seed=t + hv)
    assert gdr.gdn_tile(t, hk, hv, 128, 128, 64, BF) == (
        hv // hk, min(8, -(-t // 64)))
    got, states = through_the_op(*args, chunk=64)
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 0.012, (name, rel(a, b))
    monkeypatch.setattr(gdr, "_INTERPRET", False)      # no tile: XLA ops
    want, want_states = through_the_op(*args, chunk=64)
    assert states.shape == want_states.shape == (-(-t // 64), 1, hv, 128,
                                                 128)
    assert rel(states, want_states) < 0.01
    for name, a, b in zip(NAMES, got, want):
        assert rel(a, b) < 0.012, (name, rel(a, b))


@pytest.mark.parametrize("heads,chunks", [(1, 1), (1, 8), (2, 8), (3, 8),
                                          (4, 3), (8, 8)])
def test_the_vmem_count_covers_the_blocks_and_the_scratch(heads, chunks):
    """``_vmem_bytes`` (what ``gdn_tile`` holds to the cap and the calls
    raise Mosaic's limit by) is not under what the backward call's
    blocks, double-buffered, and its scratch take when each is laid out
    in (8, 128) tiles of 32 bits."""
    def tiled(shape, dtype):
        item = jnp.dtype(dtype).itemsize
        shape = [d for d in shape if d is not None]
        rows = -(-shape[-2] // (32 // item)) * (32 // item)
        lanes = -(-shape[-1] // 128) * 128
        return int(np.prod(shape[:-2])) * rows * lanes * item

    qk, v, _, gate, st = gdr._specs(heads, chunks, 128, 128, lambda c: c)
    blocks = sum(tiled(spec.block_shape, dt) for spec, dt in (
        [(qk, BF)] * 4 + [(v, BF)] * 3 + [(st, BF)] + [(gate, F32)] * 4))
    scratch = sum(tiled(x.shape, x.dtype) for x in gdr._scratch(
        heads, chunks, 128, 128, BF, True))
    forward = sum(tiled(x.shape, x.dtype) for x in gdr._scratch(
        heads, chunks, 128, 128, BF, False))
    assert forward < scratch
    assert gdr._vmem_bytes(heads, chunks, 128, 128) >= 2 * blocks + scratch


def test_the_cells_call_keeps_its_tile_under_the_cap():
    """b1 t8192 hk16 hv32: a key head's two value heads and 8 chunks a
    grid step, whatever the pre-pass keeps in VMEM; the limit the calls
    ask Mosaic for stays inside the chip's 128 MiB."""
    assert gdr.gdn_tile(8192, 16, 32, 128, 128, 64, BF, "tpu", False) == (2, 8)
    assert gdr._vmem_bytes(2, 8, 128, 128) <= gdr._VMEM_CAP_BYTES
    assert gdr._vmem_limit(2, 8, 128, 128) <= 128 * 2**20
    assert gdr._vmem_limit(8, 8, 128, 128) <= 128 * 2**20


@pytest.mark.parametrize("t,hk,hv", [(150, 1, 2), (600, 1, 1)])
def test_float32_operands_show_the_same_mathematics(t, hk, hv, interpreted):
    """The kernels' algebra without bf16's rounding (the picker gives
    float32 operands no tile: called directly): the recurrence to
    float32's rounding, every gradient."""
    args = operands(t, hk, hv, seed=1, dtype=F32)
    q, k, v, g, beta, do = args
    tile = gdr.gdn_tile(t, hk, hv, 128, 128, 64, BF)
    with jax.default_matmul_precision("highest"):
        o, states = gdr.gated_delta_rule_fwd(q, k, v, g, beta, tile)
        grads = gdr.gated_delta_rule_bwd(q, k, v, g, beta, states, do, tile)
    for name, a, b in zip(NAMES, (o, *grads), recurrence(*args)):
        assert rel(a, b) < 2e-5, (name, rel(a, b))


def test_states_are_the_chunked_forms(interpreted, monkeypatch):
    args = operands(256, 1, 2, seed=2)
    got, states = through_the_op(*args, chunk=64)
    monkeypatch.setattr(gdr, "_INTERPRET", False)      # no tile: XLA ops
    want, want_states = through_the_op(*args, chunk=64)
    assert states.shape == want_states.shape
    assert states.dtype == want_states.dtype
    assert rel(states, want_states) < 0.01
    assert bool((states[0] == 0).all())
    for name, a, b in zip(NAMES, got, want):
        assert rel(a, b) < 0.012, (name, rel(a, b))


def test_strong_decay_stays_finite(interpreted):
    """g down to -21 a position (A = 16, the initialiser's largest, as
    tests/test_linear_attention.py's g x 10.5): exp of the running sum
    underflows inside a chunk and no masked, positive difference
    reaches an exp."""
    q, k, v, g, beta, do = operands(128, 1, 2, seed=3)
    args = (q, k, v, g * 42.0, beta, do)
    got, _ = through_the_op(*args, chunk=64)
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert rel(a, b) < 0.012, (name, rel(a, b))


def test_repeated_keys_with_beta_near_one(interpreted, monkeypatch):
    """k_i equal inside a chunk, beta 0.98-1, almost no decay: A is
    nearly the all-ones triangle, whose powers grow to binomial size
    (the closed product of (I + A^{2^i}) cancels them in float32 and is
    wrong here). The substitution forms no power: in float32 the
    kernels sit as near the recurrence as the chunked form's triangular
    solve does, every gradient."""
    q, k, v, g, beta, do = operands(128, 1, 2, seed=4, dtype=F32)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, g * 0.01, 0.98 + 0.02 * beta, do)
    tile = gdr.gdn_tile(128, 1, 2, 128, 128, 64, BF)
    want = recurrence(*args)
    with jax.default_matmul_precision("highest"):
        o, states = gdr.gated_delta_rule_fwd(*args[:5], tile)
        got = (o, *gdr.gated_delta_rule_bwd(*args[:5], states, do, tile))
        monkeypatch.setattr(gdr, "_INTERPRET", False)
        chunked, _ = through_the_op(*args, chunk=64)
    for name, a, c, b in zip(NAMES, got, chunked, want):
        # (dg is a difference of large terms here: 1e-3 of its largest
        # entry both ways, 1e-6 for the others)
        assert rel(a, b) < max(2 * rel(c, b), 1e-5), (name, rel(a, b),
                                                       rel(c, b))


def triangles(kind, n, seed=0):
    """n strictly lower [64, 64] float32: random entries; equal keys
    with beta near 1 (entries 0.98-1 down whole columns: the powers of
    such a triangle grow to binomial size); g down to -21 a position
    (most entries underflow); entries near -1 (the inverse doubles down
    every column, to 1e18); zeros."""
    r = np.random.RandomState(seed)
    if kind == "random":
        a = r.randn(n, 64, 64) * 0.3
    elif kind == "repeated_keys":
        a = (0.98 + 0.02 * r.rand(n, 64, 1)) * np.ones((1, 1, 64))
    elif kind == "strong_decay":
        gc = np.cumsum(-21.0 * r.rand(n, 64), axis=1)
        a = r.randn(n, 64, 64) * np.exp(
            np.minimum(gc[:, :, None] - gc[:, None, :], 0.0))
    elif kind == "growth":
        a = -(0.9 + 0.1 * r.rand(n, 64, 64))
    else:
        a = np.zeros((n, 64, 64))
    return np.tril(a, -1).astype(np.float32)


def rank_one_substitution(a):
    """The form ``_invert`` had until PR 46, in numpy's float32: row j
    of T, final since step j - 1, times A[i, j] leaves every row i > j."""
    t = np.broadcast_to(np.eye(64, dtype=np.float32), a.shape).copy()
    for j in range(63):
        t[:, j + 1:, :] -= a[:, j + 1:, j:j + 1] * t[:, j:j + 1, :]
    return t


def inverted(a, heads, chunks):
    """``_invert`` alone through the interpreter on the triangles of
    ``heads`` value heads x ``chunks`` chunks, laid out as ``_triangles``
    leaves them and read back as the chunk loops read T."""
    scratch = gdr._scratch(heads, chunks, 128, 128, BF, False)[1:4]
    packed = np.zeros(scratch[0].shape, np.float32)
    for r in range(heads):
        for c in range(chunks):
            pair, half = gdr._slot(r, c, chunks)
            packed[pair, :, half * 64:(half + 1) * 64] = a[r * chunks + c]

    def kernel(a_ref, out_ref, t_ref, x_ref):
        gdr._invert(a_ref, t_ref, x_ref)
        for r in range(heads):
            for c in range(chunks):
                out_ref[r * chunks + c] = t_ref[gdr._slot(r, c, chunks)]

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(a.shape, F32),
        scratch_shapes=scratch[1:], interpret=True)(jnp.asarray(packed))


@pytest.mark.parametrize("kind", ["random", "repeated_keys", "strong_decay",
                                  "growth", "zero"])
@pytest.mark.parametrize("heads,chunks", [(1, 1), (2, 8), (4, 3)])
def test_the_inverse_alone_is_float64s_to_float32_rounding(heads, chunks,
                                                           kind):
    """T = (I + A)^-1 as the blocks and merges leave it against
    numpy's float64 inverse, every entry within float32's rounding of
    what the triangle itself makes of a rounding error, |T| (I + |A|)
    |T| (the bound a triangular inverse by substitution meets, Higham
    ch. 14: the rank-1 substitution it replaced is held to the same
    line), for one matrix, a grid step of the cell's and an odd pair."""
    n = heads * chunks
    a = triangles(kind, n, seed=n)
    want = np.tril(np.linalg.inv(np.eye(64) + a.astype(np.float64)))
    grown = np.abs(want) @ (np.eye(64) + np.abs(a)) @ np.abs(want)
    # (+ 64 of float32's smallest: a row of products that underflow)
    bound = np.finfo(np.float32).eps * grown + 64 * np.finfo(np.float32).tiny
    got = np.asarray(inverted(a, heads, chunks))
    for name, t in (("blocked", got), ("rank-1", rank_one_substitution(a))):
        assert np.isfinite(t).all(), name
        worst = float((np.abs(t - want) / bound).max())
        assert worst < 1.0, (name, worst)
    assert bool((got[:, np.triu_indices(64, 1)[0],
                     np.triu_indices(64, 1)[1]] == 0).all())
    assert bool((got[:, np.arange(64), np.arange(64)] == 1).all())


# (t, hk, hv, dk, dv, chunk, dtype, backend, on_mesh) -> tile
PICKS = {
    "the_cell": ((8192, 16, 32, 128, 128, 64, BF, "tpu", False), (2, 8)),
    "hk_equals_hv": ((4096, 8, 8, 128, 128, 64, BF, "tpu", False), (1, 8)),
    "fewer_chunks_than_a_step": ((200, 2, 4, 128, 128, 64, BF, "tpu", False),
                                 (2, 4)),
    "cpu_backend": ((8192, 16, 32, 128, 128, 64, BF, "cpu", False), None),
    "under_a_mesh": ((8192, 16, 32, 128, 128, 64, BF, "tpu", True), None),
    "float32": ((8192, 16, 32, 128, 128, 64, F32, "tpu", False), None),
    "dk_64": ((8192, 16, 32, 64, 128, 64, BF, "tpu", False), None),
    "dv_256": ((8192, 16, 32, 128, 256, 64, BF, "tpu", False), None),
    "chunk_32": ((8192, 16, 32, 128, 128, 32, BF, "tpu", False), None),
    "ragged_group": ((8192, 3, 4, 128, 128, 64, BF, "tpu", False), None),
    "group_over_the_vmem_cap": ((8192, 1, 64, 128, 128, 64, BF, "tpu",
                                 False), None),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_by_shape_dtype_backend_and_mesh(case):
    args, want = PICKS[case]
    assert gdr.gdn_tile(*args) == want
    if want:
        assert gdr._vmem_bytes(*want, 128, 128) <= gdr._VMEM_CAP_BYTES


def test_no_backend_no_tile():
    """This process's backend is the CPU and the interpreter is off:
    every call of the suite's other files runs the chunked XLA form."""
    assert not gdr.kernels_enabled()
    assert gdr.gdn_tile(8192, 16, 32, 128, 128, 64, BF) is None


def _layer_program(t, hk, hv, width, amp):
    q, k, v, g, beta, probe = operands(t, hk, hv, seed=5, dtype=F32)
    q, k, v, probe = (x[..., :width] for x in (q, k, v, probe))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def data(name, x):
            var = layers.data(name, shape=list(x.shape), dtype="float32",
                              append_batch_size=False)
            var.stop_gradient = False
            return var

        o = layers.gated_delta_rule(
            data("q", q), data("k", k), data("v", v), data("g", g),
            data("beta", beta), chunk=64, impl="chunked")
        loss = layers.reduce_sum(layers.elementwise_mul(o, data("p", probe)))
        append_backward(loss)
    main._amp = amp
    feed = dict(q=q, k=k, v=v, g=g, beta=beta, p=probe)
    return fluid.Executor().run(
        main, feed={n: np.asarray(x) for n, x in feed.items()},
        scope=fluid.Scope(),
        fetch_list=[o] + [f"{n}@GRAD" for n in ("q", "k", "v", "g", "beta")])


def test_through_the_program_under_amp_and_the_counter(interpreted):
    """AMP casts Q, K, V to bf16 and keeps G, Beta: the call gets a
    tile, both passes, and the counter says ``kernel`` with one chunk
    value; the same program in float32, and one at a width off the
    lanes, run the XLA ops and say ``chunked``."""
    monitor.reset()   # the counter is the process's, not this file's
    flags.set_flags({"telemetry": True})
    try:
        got = _layer_program(130, 1, 2, 128, amp=True)
        want = _layer_program(130, 1, 2, 128, amp=False)
        _layer_program(130, 1, 2, 64, amp=True)
        counts = L.dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    wide, narrow = ("b1 t130 hk1 hv2 dk%d dv%d" % (w, w) for w in (128, 64))
    assert counts == {f"kernel fwd {wide} chunk64": 1,
                      f"kernel bwd {wide} chunk64": 1,
                      f"chunked fwd {wide} chunk64": 1,
                      f"chunked bwd {wide} chunk64": 1,
                      f"chunked fwd {narrow} chunk64": 1,
                      f"chunked bwd {narrow} chunk64": 1}
    assert got[0].dtype.itemsize == 2
    assert got[4].dtype == np.float32 and got[5].dtype == np.float32
    for name, a, b in zip(NAMES, got, want):
        assert rel(a, b) < 0.03, (name, rel(a, b))
