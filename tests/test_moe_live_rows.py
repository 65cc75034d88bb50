"""A held share's expert layer goes with its LIVE rows (ops/moe_ops.py):
every pass over the n * k row buffer is a loop over the windows that
hold a live row, the trip count the step's own sum(Rows). Held here
against the same ops walking the whole buffer (one window of n * k rows)
and against the float32 reference's SwiGLU (perf/reference), by the live
count the routing makes; that two routings share one executable; that
nothing reads what a grouped matmul's kernel leaves behind the last
live row; what ``pt_moe_rows_dispatch_total`` says of a held layer, of
an unheld one and of a held one whose passes walk the buffer; and the
buffers' contract (PR 63): where the layer's matmuls are kernels no
buffer is filled, every buffer is finite to the end of the row tile the
last live row lies in and not defined behind it (NaN under the
interpreter hook), everything the layer hands out is the filled
build's to the bit, and ``pt_moe_buffer_fills_total`` counts what is
still filled (every carry where no kernel runs: those buffers keep
zeros behind, as they had)."""

import functools

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
    # no kernel runs at this size (a CPU, widths off the lanes): every
    # carry is the zeros it was, ``ragged_dot`` leaves zeros behind, and
    # what an op hands on keeps zeros behind the last live row (the
    # contract where kernels run: the tests at KERNEL_ATTRS below)
    for key in BUFFERS:
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


# --- where the layer's grouped matmuls are kernels: nothing is filled ------

KN, KD, KF, KK = 256, 128, 128, 4
K_SCORED, K_FIRST, K_COUNT = 8, 2, 4        # half of the experts held
KM = KN * KK                                # 1024 rows, 512 expected live
KERNEL_ATTRS = {"num_experts": K_SCORED, "held_first": K_FIRST,
                "held_count": K_COUNT}
ADAM = {"adam_op": "adam", "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
HANDED_OUT = ("out", "d_x", "d_top_w")


def kernel_window():
    return moe_ops.live_window(KM, KM * K_COUNT // K_SCORED)


def kernel_live_counts():
    w = kernel_window()
    return {"none": 0, "a_sixteenth": KM // 16, "windows_exactly": 3 * w,
            "one_row_short": 3 * w - 1, "one_row_over": 3 * w + 1,
            "all_pairs": KM}


def kernel_routing(live, seed=0):
    """``routing`` at the kernels' size: TopI [KN, KK] with exactly
    ``live`` pairs on the held experts, and TopW."""
    r = np.random.RandomState(seed)
    elsewhere = np.r_[0:K_FIRST, K_FIRST + K_COUNT:K_SCORED]
    top_i = np.stack([r.permutation(elsewhere)[:KK] for _ in range(KN)])
    for pair in r.permutation(KM)[:live]:
        t, j = divmod(int(pair), KK)
        top_i[t, j] = K_FIRST + (t + j) % K_COUNT
    assert ((top_i >= K_FIRST) & (top_i < K_FIRST + K_COUNT)).sum() == live
    assert all(len(set(row)) == KK for row in top_i)
    return (jnp.asarray(top_i, jnp.int32),
            jnp.asarray(r.rand(KN, KK), jnp.float32))


def kernel_weights(seed=2):
    r = np.random.RandomState(seed)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    v = {"x": f32(r.randn(KN, KD)), "g": f32(r.randn(KN, KD)),
         "WGate": f32(r.randn(K_COUNT, KD, KF) * 0.1),
         "WUp": f32(r.randn(K_COUNT, KD, KF) * 0.1),
         "WDown": f32(r.randn(K_COUNT, KF, KD) * 0.1)}
    for slot in ("WGate", "WUp", "WDown"):
        v[slot + ".m1"] = f32(r.randn(*v[slot].shape) * 0.01)
        v[slot + ".m2"] = f32(np.abs(r.randn(*v[slot].shape)) * 0.01)
    return v


def kernel_layer(v, top_w, top_i, gated, dtype):
    """The held layer's ops and grad ops at a size the kernels take,
    the experts' float inputs in ``dtype`` (bf16: as core/interp casts
    them under AMP; the kernels' dtype), the matrices' Adam folded into
    the experts' grad op. -> (what the layer hands out: the output,
    GRAD::X, GRAD::TopW and the Adam state of every matrix; the buffers
    its ops hand on)."""
    def cast(a):
        return a.astype(dtype)

    slots = ("WGate", "WUp", "WDown") if gated else ("WUp", "WDown")
    attrs = dict(KERNEL_ATTRS, gated=gated, act="silu" if gated else "relu2")
    x = cast(v["x"])
    disp_in = {"X": x, "TopI": top_i}
    disp = op("moe_dispatch", disp_in, attrs)
    exp_in = {"Xs": disp["Xs"], "Rows": disp["Rows"], "X": x,
              "Order": disp["Order"],
              **{slot: cast(v[slot]) for slot in slots}}
    exp = op("moe_experts", exp_in, attrs)
    comb_in = {"Ys": exp["Ys"], "TopW": top_w, "Order": disp["Order"],
               "Slot": disp["Slot"], "Like": x, "Rows": disp["Rows"]}
    out = op("moe_combine", comb_in, attrs)["Out"]
    d_comb = op("moe_combine_grad", {
        **comb_in, "Out": out, "GRAD::Out": v["g"].astype(out.dtype)}, attrs)
    one = jnp.ones((1,), jnp.float32)
    d_exp = get_op_def("moe_experts_grad").compute({
        **{k: [a] for k, a in {**exp_in, **exp}.items()},
        "GRAD::Ys": [d_comb["GRAD::Ys"]],
        "Param": [v[s] for s in slots],
        "Moment1": [v[s + ".m1"] for s in slots],
        "Moment2": [v[s + ".m2"] for s in slots],
        "Beta1Pow": [one * 0.9] * len(slots),
        "Beta2Pow": [one * 0.999] * len(slots),
        "LearningRate": [one * 1e-2] * len(slots)},
        dict(attrs, adam_slots=slots, **ADAM))
    d_x = op("moe_dispatch_grad", {
        "X": x, "Slot": disp["Slot"], "Rows": disp["Rows"],
        "GRAD::Xs": d_exp["GRAD::Xs"][0]}, attrs)["GRAD::X"]
    handed = {"out": out, "d_x": d_x, "d_top_w": d_comb["GRAD::TopW"]}
    for name in ("ParamOut", "Moment1Out", "Moment2Out"):
        for slot, value in zip(slots, d_exp[name]):
            handed[f"{slot}.{name}"] = value
    buffers = {"xs": disp["Xs"], "ys": exp["Ys"], "up": exp["Up"],
               "d_ys": d_comb["GRAD::Ys"], "d_xs": d_exp["GRAD::Xs"][0]}
    if gated:
        buffers["gate"] = exp["Gate"]
    return handed, buffers


def as_numpy(arrays):
    return {k: np.asarray(a, np.float32) for k, a in arrays.items()}


@functools.cache
def kernel_step(gated, dtype, build):
    """``kernel_layer`` as ONE executable a (gated, dtype, build): the
    live count is a device value, so the six routings of a pair run
    what the first of them traced, under the ``build``'s hooks (what
    ``test_four_routings_run_one_executable...`` holds)."""
    return jax.jit(lambda v, top_w, top_i: kernel_layer(
        v, top_w, top_i, gated, jnp.dtype(dtype)))


@pytest.fixture
def hooked(monkeypatch):
    """The kernels through the interpreter, and with them the hook:
    what ``unfilled`` hands out and what a kernel's result holds where
    no step wrote is NaN."""
    from paddle_tpu.parallel import pair_sum

    monkeypatch.setattr(moe_ops._gm, "_INTERPRET", True)
    monkeypatch.setattr(pair_sum, "_INTERPRET", True)


def filled_build(monkeypatch):
    """The layer as it was before PR 63: every carry zeros, every
    handed-on product written into zeros."""
    gm = moe_ops._gm
    gmm = gm.gmm
    monkeypatch.setattr(gm, "unfilled", jnp.zeros)
    monkeypatch.setattr(gm, "gmm", lambda *a, zero_behind=False, **kw: gmm(
        *a, zero_behind=bool(zero_behind), **kw))


K_CASES = sorted(kernel_live_counts())


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("case", K_CASES)
def test_no_buffer_is_filled_and_the_layer_is_the_filled_builds_to_the_bit(
        case, gated, dtype, hooked, monkeypatch):
    """With NaN wherever nothing wrote, whatever the layer hands out is
    the filled build's to the bit, by live count; every buffer is finite
    (zeros) from the last live row to the end of its row tile; and the
    hook bites: bf16 buffers DO hold NaN behind that tile (float32 runs
    no kernel: its carries are zeros and it fills as it did)."""
    live = kernel_live_counts()[case]
    top_i, top_w = kernel_routing(live)
    v = kernel_weights()
    tm = moe_ops._gm.row_tile(KM, K_COUNT, dtype, live_rows=KM // 2)
    assert tm == (128 if dtype == "bfloat16" else None)
    assert kernel_window() % 128 == 0
    got, buffers = map(as_numpy, kernel_step(gated, dtype, "unfilled")(
        v, top_w, top_i))
    filled_build(monkeypatch)
    want, want_buffers = map(as_numpy, kernel_step(gated, dtype, "filled")(
        v, top_w, top_i))
    assert sorted(got) == sorted(want) and len(got) == 3 + 3 * (2 + gated)
    for key in want:
        assert np.isfinite(got[key]).all(), key
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    end = -(-live // 128) * 128      # of the last live row's tile
    for key, a in buffers.items():
        assert a.shape[0] == KM
        np.testing.assert_array_equal(a[:live], want_buffers[key][:live],
                                      err_msg=key)
        assert not want_buffers[key][live:].any(), key
        if live or key in ("ys", "up", "gate"):
            # (no live row: a grouped matmul still zeroes tile 0, which
            # its idle grid writes back; a loop makes no trip)
            assert not a[live:max(end, 128 * (live == 0))].any(), key
    behind = {key for key, a in buffers.items()
              if np.isnan(a[max(end, 128):]).any()}
    if dtype == "bfloat16" and live <= KM - 2 * kernel_window():
        assert behind == set(buffers)
    elif dtype == "float32":
        assert not behind


def test_four_routings_run_one_executable_where_nothing_is_filled(
        hooked, monkeypatch):
    """As ``test_two_routings_run_one_executable``, at the kernels' size
    with the hook on: one compiled program for no live row, a sixteenth,
    three windows and a row, and every pair, each the filled build's."""
    v, bf = kernel_weights(), jnp.bfloat16
    step = jax.jit(
        lambda top_w, top_i: kernel_layer(v, top_w, top_i, True, bf)[0])
    lives = (0, KM // 16, 3 * kernel_window() + 1, KM)
    got = [step(*reversed(kernel_routing(live, seed=live)))
           for live in lives]
    assert step._cache_size() == 1
    filled_build(monkeypatch)
    for live, handed in zip(lives, got):
        top_i, top_w = kernel_routing(live, seed=live)
        want = as_numpy(kernel_step(True, "bfloat16", "filled")(
            v, top_w, top_i)[0])
        handed = as_numpy(handed)
        for key in want:
            assert np.isfinite(handed[key]).all(), (live, key)
            np.testing.assert_array_equal(handed[key], want[key],
                                          err_msg=f"{live} {key}")


def fills_of(build, *args):
    """(the fills counter's rows, the passes counter's, what
    ``lower.filled_moe_buffers.train`` reads) after one lowering."""
    from perf import harness

    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        build(*args)
        return (moe_ops.buffer_fill_counts(), moe_ops.rows_dispatch_counts(),
                harness.reader_for("lower.filled_moe_buffers.train").read(
                    None))
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def build_kernel_layer(held, amp=True):
    """A held layer at the kernels' size under ``minimize``, lowered
    (and run) once."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[KN, KD], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        # (through a projection: the layer's tokens are bf16 under AMP,
        # as a model's are)
        h = layers.fc(x, KD, num_flatten_dims=1, bias_attr=False)
        out, *_ = layers.topk_moe(h, K_SCORED, KK, KF, name="m", held=held)
        fluid.optimizer.Adam(1e-3).minimize(
            layers.reduce_sum(layers.square(out)))
    main._amp = amp
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((KN, KD), np.float32)}, scope=scope,
            fetch_list=[out])


def test_the_fills_counter_reads_nothing_where_kernels_run(hooked):
    fills, rows, metric = fills_of(build_kernel_layer, (K_FIRST, K_COUNT))
    assert fills == {} and metric == 0
    assert any(" windowed " in row for row in rows)


@pytest.mark.parametrize("how", ["no_kernel", "float32", "under_a_mesh",
                                 "unheld"])
def test_the_fills_counter_counts_every_carry_where_none_runs(
        how, monkeypatch):
    """No TPU (and no hook), rows that are not bf16, or a program under
    a mesh (the kernels refuse their tiles there): the layer lowers as
    it did, eight carries of zeros (Xs twice, h twice, dgate, dup,
    GRAD::Xs, GRAD::Ys), each a row of the counter. A layer that holds
    every expert has no carry and no row."""
    if how in ("float32", "under_a_mesh"):
        monkeypatch.setattr(moe_ops._gm, "_INTERPRET", True)
    if how == "under_a_mesh":
        from paddle_tpu.parallel import pair_sum

        monkeypatch.setattr(moe_ops._gm, "_under_mesh", lambda: True)
        monkeypatch.setattr(pair_sum, "_under_mesh", lambda: True)
        assert not moe_ops._gm.unfilled((8, 128), jnp.bfloat16).any()
    held = None if how == "unheld" else (K_FIRST, K_COUNT)
    fills, rows, metric = fills_of(build_kernel_layer, held,
                                   how != "float32")
    if how == "unheld":
        assert fills == {} and metric is None
        assert all(" whole " in row for row in rows)
        return
    assert metric == 8
    d, f = f"{KM}x{KD}", f"{KM}x{KF}"
    assert fills == {
        f"moe_dispatch Xs {d}": 1, f"moe_experts h {f}": 1,
        f"moe_combine_grad GRAD::Ys {d}": 1,
        f"moe_experts_grad Xs {d}": 1, f"moe_experts_grad h {f}": 1,
        f"moe_experts_grad dgate {f}": 1, f"moe_experts_grad dup {f}": 1,
        f"moe_experts_grad GRAD::Xs {d}": 1}


def test_a_window_that_is_not_whole_row_tiles_keeps_the_fills(
        hooked, monkeypatch):
    """The contract leans on the window being whole row tiles of the
    kernels' (the last trip's zeros reach the tile's end): ``_carry``
    looks, and a layer whose window is shorter fills as it did."""
    monkeypatch.setattr(moe_ops, "live_window", lambda m, live_rows: 64)
    v = kernel_weights()
    top_i, top_w = kernel_routing(3 * 64 + 1)
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        got, buffers = map(as_numpy, kernel_layer(v, top_w, top_i, True,
                                                  jnp.bfloat16))
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    for key in ("xs", "d_ys", "d_xs"):      # the carries: zeros behind
        assert not buffers[key][3 * 64 + 1:].any(), key
    for key in ("ys", "up", "gate"):        # the products: to the tile
        assert not buffers[key][3 * 64 + 1:256].any(), key
    assert all(np.isfinite(a).all() for a in got.values())
