"""The causal convolution's Pallas kernels
(paddle_tpu/parallel/causal_conv.py) on the CPU through the Pallas
interpreter: forward, dX and dW against the XLA form they replace
(ops/linear_attention_ops._conv_xla and jax's vjp of it) over the
blocks, passes, lane blocks, taps and activations a call can have; the
rows a block takes from its neighbour; the picker's table; the dispatch
counter; and the op and its grad op through a Program under AMP. The
chip's run is chip_smoke.py's ``gdn`` phase.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.parallel import causal_conv as cc

BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(cc, "_INTERPRET", True)


def operands(b, t, c, taps, seed=0, dtype=BF):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, c), dtype),
            jnp.asarray(r.randn(c, taps) * 0.5, F32),
            jnp.asarray(r.randn(b, t, c), dtype))


def through_the_op(x, w, dy, act):
    """(Y, dX, dW): the registered op and its grad op, as the Program
    runs them."""
    ins = {"X": [x], "W": [w]}
    y = L._causal_conv1d(ins, {"act": act})["Y"][0]
    grads = L._causal_conv1d_grad({**ins, "Y": [y], "GRAD::Y": [dy]},
                                  {"act": act})
    return y, grads["GRAD::X"][0], grads["GRAD::W"][0]


def xla_form(x, w, dy, act):
    y, vjp = jax.vjp(lambda x, w: L._conv_xla(x, w, act), x, w)
    return (y, *vjp(dy))


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


# (batch, positions, channels, taps, act) -> the tile: one block of one
# pass; one block of 32 passes; three blocks, the last ragged, over three
# lane blocks; t shorter than a pass; two lane blocks of 512 and two
# rows of a batch; 2 taps over two blocks; no activation; 9 taps
CASES = {
    "one_pass": ((1, 32, 128, 4, "silu"), (32, 128)),
    "one_block": ((1, 1024, 128, 4, "silu"), (1024, 128)),
    "ragged_blocks_lanes3": ((1, 2100, 384, 4, "silu"), (1024, 128)),
    "shorter_than_a_pass": ((1, 20, 256, 4, "silu"), (32, 256)),
    "batch2_lanes512": ((2, 200, 1024, 4, "silu"), (224, 512)),
    "taps2": ((1, 1200, 128, 2, "silu"), (1024, 128)),
    "no_act": ((2, 600, 256, 4, ""), (608, 256)),
    "taps9_no_act": ((1, 130, 128, 9, ""), (160, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_xla_form(case, interpreted):
    """Y and dX to bf16's rounding of a result (the float32 sums differ
    by the order of their additions at most), dW to float32's."""
    (b, t, c, taps, act), tile = CASES[case]
    x, w, dy = operands(b, t, c, taps, seed=t)
    assert cc.conv_tile(t, c, taps, BF) == tile
    got = through_the_op(x, w, dy, act)
    want = xla_form(x, w, dy, act)
    assert got[0].dtype == got[1].dtype == BF and got[2].dtype == F32
    for name, a, b_, tol in zip(("y", "dx", "dw"), got, want,
                                (0.01, 0.01, 1e-5)):
        assert a.shape == b_.shape, name
        assert rel(a, b_) < tol, (name, rel(a, b_))


def test_float32_shows_the_same_mathematics(interpreted):
    """The kernels' algebra without bf16's rounding (the picker gives
    float32 no tile: called directly)."""
    x, w, dy = operands(2, 300, 128, 4, seed=1, dtype=F32)
    tile = cc.conv_tile(300, 128, 4, BF)
    got = (cc.causal_conv_fwd(x, w, tile),
           *cc.causal_conv_bwd(x, w, dy, tile))
    for name, a, b in zip(("y", "dx", "dw"), got, xla_form(x, w, dy, "silu")):
        assert rel(a, b) < 1e-5, (name, rel(a, b))


@pytest.mark.parametrize("edge", [1024, 32], ids=["blocks", "passes"])
def test_a_block_takes_its_first_rows_from_the_block_in_front(edge,
                                                              interpreted):
    """X is zero but for the three rows in front of a block's (a
    pass's) edge: with four taps of ones and no activation the three
    rows behind the edge are sums of them and nothing else is; the
    mirror: dY lives on the three rows behind the edge alone, and dX of
    the three in front is a sum of them."""
    t, c = 2 * 1024, 128
    w = jnp.ones((c, 4), F32)
    x = jnp.zeros((1, t, c), BF).at[:, edge - 3:edge].set(
        jnp.asarray([1.0, 2.0, 4.0], BF)[None, :, None])
    tile = cc.conv_tile(t, c, 4, BF)
    assert tile == (1024, 128)
    y = np.asarray(cc.causal_conv_fwd(x, w, tile, ""), np.float32)[0, :, 0]
    assert list(y[edge - 3:edge + 3]) == [1, 3, 7, 7, 6, 4]
    assert not y[:edge - 3].any() and not y[edge + 3:].any()
    dy = jnp.zeros((1, t, c), BF).at[:, edge:edge + 3].set(
        jnp.asarray([1.0, 2.0, 4.0], BF)[None, :, None])
    dx, dw = cc.causal_conv_bwd(x, w, dy, tile, "")
    dx = np.asarray(dx, np.float32)[0, :, 0]
    assert list(dx[edge - 3:edge + 3]) == [1, 3, 7, 7, 6, 4]
    assert not dx[:edge - 3].any() and not dx[edge + 3:].any()
    # dW[c, j] = sum_r x[r - 3 + j] dy[r]: x's rows meet dy's at j < 3
    assert np.asarray(dw)[0].tolist() == [1 * 1 + 2 * 2 + 4 * 4,
                                          2 * 1 + 4 * 2, 4 * 1, 0]


# (t, c, taps, dtype, backend, on_mesh) -> tile
PICKS = {
    "the_cell": ((8192, 8192, 4, BF, "tpu", False), (1024, 512)),
    "c_384": ((8192, 384, 4, BF, "tpu", False), (1024, 128)),
    "c_768": ((4096, 768, 4, BF, "tpu", False), (1024, 256)),
    "short": ((100, 256, 2, BF, "tpu", False), (128, 256)),
    "taps_9": ((8192, 8192, 9, BF, "tpu", False), (1024, 512)),
    "cpu_backend": ((8192, 8192, 4, BF, "cpu", False), None),
    "under_a_mesh": ((8192, 8192, 4, BF, "tpu", True), None),
    "float32": ((8192, 8192, 4, F32, "tpu", False), None),
    "float16": ((8192, 8192, 4, jnp.float16, "tpu", False), None),
    "c_off_the_lanes": ((8192, 8200, 4, BF, "tpu", False), None),
    "c_64": ((8192, 64, 4, BF, "tpu", False), None),
    "taps_10": ((8192, 8192, 10, BF, "tpu", False), None),
    "no_rows": ((0, 8192, 4, BF, "tpu", False), None),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_by_shape_dtype_backend_and_mesh(case):
    args, want = PICKS[case]
    assert cc.conv_tile(*args) == want
    if want:
        assert cc._vmem_bytes(*want, args[2]) <= cc._VMEM_CAP_BYTES


def test_a_block_over_the_vmem_cap_gets_a_narrower_one_or_none(monkeypatch):
    cell = (8192, 8192, 4, BF, "tpu", False)
    monkeypatch.setattr(cc, "_VMEM_CAP_BYTES", cc._vmem_bytes(1024, 256, 4))
    assert cc.conv_tile(*cell) == (1024, 256)
    monkeypatch.setattr(cc, "_VMEM_CAP_BYTES",
                        cc._vmem_bytes(1024, 128, 4) - 1)
    assert cc.conv_tile(*cell) is None


def test_no_backend_no_tile():
    """This process's backend is the CPU and the interpreter is off:
    every call of the suite's other files runs the XLA form, and the op
    without a tile is the five lines it was."""
    assert not cc.kernels_enabled()
    assert cc.conv_tile(8192, 8192, 4, BF) is None
    x, w, dy = operands(1, 70, 128, 4)
    for a, b in zip(through_the_op(x, w, dy, "silu"),
                    xla_form(x, w, dy, "silu")):
        assert a.dtype == b.dtype and bool((a == b).all())


def _layer_program(t, c, amp, taps=4):
    r = np.random.RandomState(5)
    x = r.randn(1, t, c).astype(np.float32)
    probe = r.randn(1, t, c).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        xin = layers.data("x", shape=[1, t, c], dtype="float32",
                          append_batch_size=False)
        xin.stop_gradient = False
        # a matmul in front, as the mixer's projection: bf16 under AMP
        h = layers.fc(xin, c, num_flatten_dims=2, bias_attr=False,
                      param_attr=ParamAttr(name="proj.w"))
        y = layers.causal_conv1d(h, taps=taps, act="silu",
                                 param_attr=ParamAttr(name="conv.w"))
        p = layers.data("p", shape=[1, t, c], dtype="float32",
                        append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(y, p))
        append_backward(loss)
    assert "causal_conv1d_grad" in [op.type for op in main.global_block().ops]
    main._amp = amp
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    return exe.run(main, feed={"x": x, "p": probe}, scope=scope,
                   fetch_list=[y, "x@GRAD", "conv.w@GRAD", "proj.w@GRAD"])


def test_through_the_program_under_amp_and_the_counter(interpreted,
                                                       monkeypatch):
    """Under AMP the projection in front hands the conv bf16: the call
    gets a tile, both passes, and the counter says ``kernel``; the same
    program in float32, and one at channels off the lanes, run the XLA
    form and say ``xla``. The kernels' program agrees with the XLA
    form's under AMP to bf16's rounding."""
    monitor.reset()   # the counter is the process's, not this file's
    flags.set_flags({"telemetry": True})
    try:
        got = _layer_program(150, 128, amp=True)
        _layer_program(150, 128, amp=False)
        _layer_program(150, 64, amp=True, taps=2)
        counts = L.conv_dispatch_counts()
        monkeypatch.setattr(cc, "_INTERPRET", False)      # no tile
        want = _layer_program(150, 128, amp=True)
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    assert counts == {"kernel fwd b1 t150 c128 taps4": 1,
                      "kernel bwd b1 t150 c128 taps4": 1,
                      "xla fwd b1 t150 c128 taps4": 1,
                      "xla bwd b1 t150 c128 taps4": 1,
                      "xla fwd b1 t150 c64 taps2": 1,
                      "xla bwd b1 t150 c64 taps2": 1}
    assert got[0].dtype.itemsize == 2          # Y stays bf16
    assert got[2].dtype == np.float32          # dW is the parameter's
    for name, a, b in zip(("y", "dx", "dconv", "dproj"), got, want):
        assert rel(a, b) < 0.02, (name, rel(a, b))
