"""A held share's expert layer goes with its LIVE rows (ops/moe_ops.py):
every pass over the n * k row buffer is a loop over the windows that
hold a live row, the trip count the step's own sum(Rows). Held here
against the same ops walking the whole buffer (one window of n * k rows)
and against the float32 reference's SwiGLU (perf/reference), by the live
count the routing makes; that two routings share one executable; that
every buffer an op hands on has zeros behind the last live row and
nothing reads what a grouped matmul's kernel leaves there; and what
``pt_moe_rows_dispatch_total`` says of a held layer, of an unheld one
and of a held one whose passes walk the buffer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.core import autodiff
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import moe_ops
from perf.reference import qwen3next as ref

N, D, F = 64, 16, 8
ROUTER_E, FIRST, COUNT = 192, 8, 12     # a sixteenth of the experts held
ATTRS = {"num_experts": ROUTER_E, "held_first": FIRST, "held_count": COUNT}


def window(k):
    return moe_ops.live_window(N * k, -(-N * k * COUNT // ROUTER_E))


def live_counts(k):
    """The cases by name: {name: live rows}. W = 16 at both k."""
    w, m = window(k), N * k
    return {"none": 0, "a_sixteenth": m // 16, "windows_exactly": 3 * w,
            "one_row_short": 3 * w - 1, "one_row_over": 3 * w + 1,
            "all_pairs": m}


def routing(k, live, seed=0):
    """TopI [N, k] with exactly ``live`` pairs on held experts (k
    distinct experts a token, as a router gives them) and TopW [N, k]."""
    r = np.random.RandomState(seed)
    elsewhere = np.r_[0:FIRST, FIRST + COUNT:ROUTER_E]
    top_i = np.stack([r.permutation(elsewhere)[:k] for _ in range(N)])
    for pair in r.permutation(N * k)[:live]:
        t, j = divmod(int(pair), k)
        top_i[t, j] = FIRST + (t + j) % COUNT
    assert ((top_i >= FIRST) & (top_i < FIRST + COUNT)).sum() == live
    assert all(len(set(row)) == k for row in top_i)
    return jnp.asarray(top_i, jnp.int32), jnp.asarray(r.rand(N, k),
                                                      jnp.float32)


def weights(seed=1):
    r = np.random.RandomState(seed)
    return {"x": jnp.asarray(r.randn(N, D), jnp.float32),
            "wg": jnp.asarray(r.randn(COUNT, D, F) * 0.3, jnp.float32),
            "wu": jnp.asarray(r.randn(COUNT, D, F) * 0.3, jnp.float32),
            "wd": jnp.asarray(r.randn(COUNT, F, D) * 0.3, jnp.float32),
            "g": jnp.asarray(r.randn(N, D), jnp.float32)}


def op(name, ins, attrs):
    return {k: v[0] for k, v in get_op_def(name).compute(
        {k: [v] for k, v in ins.items()}, dict(attrs)).items()}


def layer(v, top_w, top_i, amp, attrs=ATTRS):
    """The held layer's three ops and their grad ops as
    ``layers.topk_moe`` wires them and ``append_backward`` pairs them,
    on the cotangent v["g"]; under ``amp`` the experts' float inputs go
    in as bf16, as core/interp casts them. -> out, GRAD::X, GRAD::TopW
    and the three weight gradients, float32."""
    cast = (lambda a: a.astype(jnp.bfloat16)) if amp else (lambda a: a)
    x = v["x"]
    disp_in = {"X": x, "TopI": top_i}
    disp = op("moe_dispatch", disp_in, attrs)
    exp_in = {"Xs": cast(disp["Xs"]), "Rows": disp["Rows"], "X": cast(x),
              "Order": disp["Order"], "WGate": cast(v["wg"]),
              "WUp": cast(v["wu"]), "WDown": cast(v["wd"])}
    exp = op("moe_experts", exp_in, attrs)
    comb_in = {"Ys": exp["Ys"], "TopW": top_w, "Order": disp["Order"],
               "Slot": disp["Slot"], "Like": x, "Rows": disp["Rows"]}
    out = op("moe_combine", comb_in, attrs)["Out"]
    d_comb = op("moe_combine_grad", {
        **comb_in, "Out": out, "GRAD::Out": v["g"].astype(out.dtype)}, attrs)
    d_exp = op("moe_experts_grad", {**exp_in, **exp,
                                    "GRAD::Ys": d_comb["GRAD::Ys"]}, attrs)
    d_disp = autodiff.make_grad_compute(get_op_def("moe_dispatch"))(
        {**{k: [a] for k, a in {**disp_in, **disp}.items()},
         "GRAD::Xs": [d_exp["GRAD::Xs"].astype(x.dtype)]},
        {**attrs, "fwd_input_slots": ["X", "TopI"],
         "fwd_output_slots": ["Xs", "Rows", "Order", "Slot"]})
    got = {"out": out, "d_x": d_disp["GRAD::X"][0],
           "d_top_w": d_comb["GRAD::TopW"], "d_wg": d_exp["GRAD::WGate"],
           "d_wu": d_exp["GRAD::WUp"], "d_wd": d_exp["GRAD::WDown"],
           "rows": disp["Rows"], "xs": disp["Xs"], "ys": exp["Ys"],
           "gate": exp["Gate"], "up": exp["Up"],
           "d_ys": d_comb["GRAD::Ys"], "d_xs": d_exp["GRAD::Xs"]}
    return {k: (a.astype(jnp.float32) if k != "rows" else a)
            for k, a in got.items()}


def reference(v, top_w, top_i):
    """The same six from the float32 reference's SwiGLU: every held
    expert on every token, weighted by the router's weight where the
    token chose it. The experts' part only: no gradient flows through
    the router here, and X's gradient is the experts' alone."""
    def loss(x, top_w_, wg, wu, wd):
        weight = jnp.einsum("nk,nke->ne", top_w_, jax.nn.one_hot(
            top_i, ROUTER_E))[:, FIRST:FIRST + COUNT]
        out = sum(weight[:, e:e + 1] * ref.swiglu(x, wg[e], wu[e], wd[e],
                                                  None)
                  for e in range(COUNT))
        return jnp.sum(out * v["g"]), out

    grads, out = jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        v["x"], top_w, v["wg"], v["wu"], v["wd"])
    return dict(zip(("d_x", "d_top_w", "d_wg", "d_wu", "d_wd"), grads),
                out=out)


BUFFERS = ("xs", "gate", "up", "ys", "d_ys", "d_xs")
CASES = [(k, name) for k in (10, 8) for name in live_counts(10)]


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize("k,case", CASES,
                         ids=[f"k{k}-{name}" for k, name in CASES])
def test_windowed_layer_is_the_whole_buffer_layer_and_the_reference(
        k, case, amp, monkeypatch):
    live = live_counts(k)[case]
    top_i, top_w = routing(k, live)
    v = weights()
    assert 8 <= window(k) < N * k // 16 + 8
    got = layer(v, top_w, top_i, amp)
    assert int(got["rows"].sum()) == live
    # the buffer keeps a row for every pair; its live rows are the held
    # pairs' tokens and nothing lies behind them
    assert got["xs"].shape == (N * k, D)
    assert not np.asarray(got["xs"][live:]).any()
    if case == "none":
        assert not any(np.asarray(got[key]).any() for key in got
                       if key != "rows")
    for key in BUFFERS:         # what an op hands on: zeros behind
        assert got[key].shape[0] == N * k
        assert not np.asarray(got[key][live:]).any(), key
    # ... against one window of n * k rows: every pass walks the buffer
    monkeypatch.setattr(moe_ops, "live_window", lambda m, live_rows: m)
    whole = layer(v, top_w, top_i, amp)
    monkeypatch.undo()
    want = reference(v, top_w, top_i)
    tol = dict(rtol=3e-2, atol=3e-2) if amp else dict(rtol=2e-5, atol=2e-5)
    for key in want:
        np.testing.assert_allclose(got[key], whole[key], rtol=1e-6,
                                   atol=1e-6, err_msg=key)
        scale = float(np.abs(np.asarray(want[key])).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(got[key]) / scale, np.asarray(want[key]) / scale,
            err_msg=key, **tol)


def test_two_routings_run_one_executable():
    """The trip count is a device value: a routing with no live row, an
    even one and one with every pair live go through ONE compiled
    program (a compile inside a measured window makes a run incorrect),
    and each is the reference's."""
    k, v = 10, weights()
    step = jax.jit(lambda top_w, top_i: layer(v, top_w, top_i, False))
    for live in (0, N * k // 16, 3 * window(k) + 1, N * k):
        top_i, top_w = routing(k, live, seed=live)
        got, want = step(top_w, top_i), reference(v, top_w, top_i)
        for key in want:
            np.testing.assert_allclose(got[key], want[key], rtol=2e-5,
                                       atol=2e-5, err_msg=f"{live} {key}")
    assert step._cache_size() == 1


@pytest.mark.parametrize("share", ["few_live", "half_live"])
def test_nothing_reads_behind_the_last_live_row(share, monkeypatch):
    """What a grouped matmul's KERNEL leaves behind the last group is
    not defined (it writes nothing there: parallel/grouped_matmul.py),
    and ``zero_behind=False`` hands that out. With NaN put there in
    every product and rows' gradient asked for that way, the layer, its
    six gradients and the buffers its ops hand on are what they were,
    zeros behind, with few rows live and with more than half (the two
    sums by token mask the pairs behind the last live row; the kernel
    that does them on a TPU is held to the same in
    tests/test_pair_sum_kernel.py)."""
    k = 8
    top_i, top_w = routing(k, {"few_live": 3 * window(k) - 5,
                               "half_live": N * k // 2 + 3}[share])
    v = weights()
    want = layer(v, top_w, top_i, False)
    gm = moe_ops._gm

    poisoned = []

    def behind(a, sizes, zero_behind=True, **kw):
        if zero_behind:
            return a
        poisoned.append(a.shape)
        rows = jnp.arange(a.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), a, jnp.nan)

    product, grads = gm.grouped_matmul, gm.grouped_matmul_grads
    monkeypatch.setattr(gm, "grouped_matmul", lambda lhs, rhs, sizes, **kw:
                        behind(product(lhs, rhs, sizes, **kw), sizes, **kw))

    def poisoned_grads(lhs, rhs, sizes, g, **kw):
        dx, dw = grads(lhs, rhs, sizes, g, **kw)
        return behind(dx, sizes, **kw), dw

    monkeypatch.setattr(gm, "grouped_matmul_grads", poisoned_grads)
    got = layer(v, top_w, top_i, False)
    # dh and the two halves of d Xs
    assert poisoned == [(N * k, F)] + [(N * k, D)] * 2
    for key in ("out", "d_x", "d_top_w", "d_wg", "d_wu", "d_wd") + BUFFERS:
        assert np.isfinite(np.asarray(got[key])).all(), key
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def build_layer(held):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        out, *_ = layers.topk_moe(x, 16, 4, F, name="m", held=held)
        fluid.backward.append_backward(
            layers.reduce_sum(layers.square(out)))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((N, D), np.float32)}, scope=scope,
            fetch_list=[out])


def test_the_rows_counter_says_windowed_for_a_held_layer_and_whole_for_another():
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        build_layer((4, 4))
        held = moe_ops.rows_dispatch_counts()
        monitor.reset()
        build_layer(None)
        unheld = moe_ops.rows_dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
    m, w = N * 4, moe_ops.live_window(N * 4, N)
    passes = {("moe_dispatch", "gather_xs"), ("moe_experts", "swiglu"),
              ("moe_experts_grad", "gather_xs"),
              ("moe_experts_grad", "swiglu"),
              ("moe_experts_grad", "swiglu_grad"),
              ("moe_experts_grad", "sum_dx"), ("moe_combine", "sum_pairs"),
              ("moe_combine_grad", "d_ys"), ("moe_combine_grad", "d_w")}
    # the two sums by token are the pairs.sum.* kernel's on a TPU and a
    # walk of the buffer by token here: the form says so
    by_token = {("moe_combine", "sum_pairs"), ("moe_dispatch_grad", "d_x")}
    assert set(held) == {
        f"{o} {p} windowed{'|by_token' * ((o, p) in by_token)} {m} w{w}"
        for o, p in passes | by_token}
    assert set(unheld) == {f"{o} {p} whole {m}" for o, p in passes | by_token}
    # moe_dispatch has a grad op of its own: its forward is traced once
    assert held[f"moe_dispatch gather_xs windowed {m} w{w}"] == 1
    assert held[f"moe_combine sum_pairs windowed|by_token {m} w{w}"] == 1


def test_a_held_pass_lowered_whole_says_so(monkeypatch):
    """The form is the lowering branch's own, not the layer's attribute:
    a held layer whose ops take no window (here: none is given them)
    walks its buffer, its rows read ``whole``, and
    ``lower.whole_buffer_moe_calls.train`` counts every one."""
    from perf import harness

    monkeypatch.setattr(moe_ops, "_window", lambda attrs, m: None)
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        build_layer((4, 4))
        held = moe_ops.rows_dispatch_counts()
        count = harness.reader_for(
            "lower.whole_buffer_moe_calls.train").read(None)
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    # (the nine passes and moe_dispatch's gradient, its sum by token)
    assert len(held) == 10 and all(" whole " in row for row in held)
    assert count == sum(held.values()) >= 10
