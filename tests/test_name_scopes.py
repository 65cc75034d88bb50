"""The program's names in the lowering (ISSUE 24): op roles and name
scopes on the ops, ``<phase>/<scope>/<op type>`` in the compiled HLO's
op_names for the two benchmarked builders, a scope being a name and
never arithmetic, the eight Pallas kernel names, and the executor's
span tree in a jax.profiler trace."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, framework, layers, monitor, unique_name
from paddle_tpu.core import interp, lowering
from paddle_tpu.models import bert as B
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import flash_attention as fa

T_CFG = dict(src_vocab_size=50, trg_vocab_size=60, max_length=32,
             d_model=32, d_inner=64, n_head=4, n_layer=2, dropout=0.1,
             label_smooth_eps=0.1)
B_CFG = dict(vocab_size=50, max_position=16, d_model=32, d_inner=64,
             n_head=4, n_layer=2, dropout=0.1)


def build(family, amp=True):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), fluid.program_guard(main, startup):
        if family == "transformer":
            cfg = T.TransformerConfig(**T_CFG)
            model = T.build(cfg)
            feed = T.make_batch(cfg, 4, 16, 16, seed=3)
        else:
            cfg = B.BertConfig(**B_CFG)
            model = B.build(cfg)
            feed = B.make_batch(cfg, 4, 16, seed=3)
        fluid.optimizer.Adam(1e-3).minimize(model["loss"])
    main._amp = amp
    return main, startup, model["loss"], feed


def op_names(main, startup, loss, feed):
    """op_name of every instruction of the compiled train step."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    lowered = lowering.lower_block(main, 0, sorted(feed), [loss.name])
    fn = fluid.Executor._jit_for(lowered, None)
    state = exe._gather_state(scope, lowered)
    text = fn.lower(state, {k: np.asarray(v) for k, v in feed.items()},
                    exe._base_key_for(main), np.uint32(0)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def losses(main, startup, loss, feed, steps=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)[0]).tobytes()
            for _ in range(steps)]


# --- roles and scopes on the ops ---------------------------------------


def test_name_scope_nests_and_a_grad_op_carries_its_forwards_scope():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        with fluid.name_scope("outer"):
            h = layers.fc(x, 8, act="relu")
            with fluid.name_scope("inner"):
                h = layers.fc(h, 4)
        loss = layers.mean(h)
        with fluid.name_scope("ambient"):   # minimize called inside one
            fluid.optimizer.SGD(0.1).minimize(loss)
    ops = main.global_block().ops
    by_type = {}
    for op in ops:
        by_type.setdefault(op.type, []).append(op)
    assert [op.namescope for op in by_type["mul"]] == ["outer",
                                                       "outer/inner"]
    assert by_type["relu"][0].namescope == "outer"
    assert by_type["mean"][0].namescope == ""
    # grad ops are built from their forward's attrs: scope inherited,
    # role overridden
    assert sorted(op.namescope for op in by_type["mul_grad"]) == [
        "outer", "outer/inner"]
    assert {op.role for op in by_type["mul_grad"]} == {"bwd"}
    assert by_type["mean_grad"][0].namescope == "ambient"
    assert {op.role for op in by_type["sgd"]} == {"opt"}
    assert framework._name_scope_ == []      # every scope closed
    # an op's kernel sees the attrs it saw before
    mul = by_type["mul_grad"][0]
    assert framework.OP_NAMESCOPE_ATTR in mul.attrs
    assert not set(framework.OP_META_ATTRS) & set(mul.compute_attrs())
    assert interp.op_scope_name(mul) == f"bwd/{mul.namescope}/mul_grad"
    assert interp.op_scope_name(by_type["mean"][0]) == "fwd/mean"


def test_roles_survive_clone_and_serialization_and_cse_ignores_scopes():
    from paddle_tpu import passes

    main, startup, loss, _ = build("transformer")
    again = fluid.Program.parse_from_string(main.desc_str())
    assert [(o.type, o.role, o.namescope) for o in again.global_block().ops] \
        == [(o.type, o.role, o.namescope) for o in main.global_block().ops]
    # two pure ops that differ only in scope are one value to CSE
    prog, st = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, st):
        x = layers.data("x", shape=[8], dtype="float32")
        with fluid.name_scope("a"):
            a = layers.scale(x, scale=2.0)
        with fluid.name_scope("b"):
            b = layers.scale(x, scale=2.0)
        out = layers.elementwise_add(a, b)
    ka, kb = [passes._op_key(o) for o in prog.global_block().ops
              if o.type == "scale"]
    assert ka == kb and out is not None


@pytest.mark.parametrize("family", ["transformer", "bert"])
def test_every_op_of_the_train_block_falls_in_exactly_one_phase(family):
    main, _, _, _ = build(family)
    ops = main.global_block().ops
    roles = [op.role for op in ops]
    assert set(roles) == {"fwd", "bwd", "opt"}
    # forward, then backward, then optimizer: the phases do not interleave
    assert roles == sorted(roles, key=("fwd", "bwd", "opt").index)
    assert all(op.role == "bwd" for op in ops
               if op.type.endswith("_grad"))
    assert all(op.role == "opt" for op in ops if op.type == "adam")
    first_bwd = roles.index("bwd")
    assert ops[first_bwd].type == "fill_any_like"   # d(loss)/d(loss)
    head = "loss_head" if family == "transformer" else "mlm_head"
    scopes = {op.namescope for op in ops}
    assert {head, "enc0/attn", "enc0/ffn", "enc1/attn"} <= scopes
    if family == "transformer":
        assert {"embed_src", "embed_trg", "dec0/self", "dec0/cross",
                "dec1/ffn"} <= scopes
    else:
        assert {"embed", "nsp_head"} <= scopes
    # parameter names stay out of scopes: a handful per layer
    assert len(scopes) <= 2 + 5 * 2 + 3


# --- the compiled step's op_names ----------------------------------------


@pytest.mark.parametrize("family", ["transformer", "bert"])
def test_the_compiled_step_carries_phase_scope_and_op_type(family):
    names = op_names(*build(family))
    head = "loss_head" if family == "transformer" else "mlm_head"

    def has(pattern):
        return any(re.search(pattern, n) for n in names)

    assert has(r"^jit\(step_fn\)/fwd/enc0/attn/mul/dot_general$")
    assert has(r"/fwd/enc1/ffn/layer_norm/")
    assert has(r"/fwd/enc0/attn/scaled_dot_product_attention/")
    assert has(r"/bwd/enc0/ffn/mul_grad/.*dot_general$")
    assert has(r"/bwd/enc1/attn/scaled_dot_product_attention_grad/")
    assert has(r"/opt/adam/")
    # BERT's head projects and scores in one op, whose loop's ops carry
    # while/body behind the op's name
    loss_op = ("softmax_with_cross_entropy" if family == "transformer"
               else "linear_cross_entropy")
    assert has(rf"/fwd/{head}/{loss_op}/")
    assert has(rf"/bwd/{head}/{loss_op}_grad/")
    assert has(rf"/bwd/{head}/mul_grad/")
    if family == "bert":
        assert has(rf"/fwd/{head}/{loss_op}/while/body/.*dot_general$")
        assert has(rf"/bwd/{head}/{loss_op}_grad/while/body/.*dot_general$")
    if family == "transformer":
        assert has(r"/fwd/dec0/cross/mul/") and has(r"/bwd/dec1/self/")
        assert has(r"/fwd/embed_trg/lookup_table")
    else:
        assert has(r"/fwd/embed/") and has(r"/fwd/nsp_head/")
    # the AMP cast and the per-op key are charged to the op that asked
    assert has(r"/fwd/enc0/attn/mul/convert_element_type$")
    assert has(r"/fwd/enc0/ffn/dropout/.*(threefry|random_bits|xor)")
    # whatever computes is named: nothing of the step's arithmetic sits
    # outside a phase
    stray = [n for n in names if n.startswith("jit(step_fn)/")
             and not re.match(r"jit\(step_fn\)/(fwd|bwd|opt)/", n)]
    assert not [n for n in stray if "dot_general" in n or "reduce" in n], \
        stray


@pytest.mark.parametrize("family", ["transformer", "bert"])
def test_a_scope_is_a_name_not_arithmetic(family, monkeypatch):
    import contextlib

    scoped = losses(*build(family))

    @contextlib.contextmanager
    def no_scope(prefix):
        yield

    monkeypatch.setattr(fluid, "name_scope", no_scope)
    main, startup, loss, feed = build(family)
    assert {op.namescope for op in main.global_block().ops} == {""}
    assert losses(main, startup, loss, feed) == scoped   # bit for bit
    assert len(set(scoped)) == len(scoped)               # it trains


def test_a_control_flow_sub_block_nests_under_its_op():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        acc = layers.fill_constant([1, 4], "float32", 0.0)
        with fluid.name_scope("loop"):
            cond = layers.less_than(i, n)
            w = layers.While(cond)
            with w.block():
                with fluid.name_scope("body"):
                    layers.assign(layers.elementwise_add(acc, x), acc)
                layers.increment(i, 1, in_place=True)
                layers.less_than(i, n, cond=cond)
    lowered = lowering.lower_block(main, 0, ["x"], [acc.name])
    fn = fluid.Executor._jit_for(lowered, None)
    exe = fluid.Executor(fluid.CPUPlace())
    text = fn.lower({}, {"x": np.ones((1, 4), np.float32)},
                    exe._base_key_for(main), np.uint32(0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(re.search(
        r"/fwd/loop/while/.*fwd/loop/body/elementwise_add/add$", n)
        for n in names), sorted(names)


# --- the Pallas kernel names ---------------------------------------------


def pallas_names(f, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for v in eqn.params.values():
                for x in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


def test_the_nine_pallas_calls_carry_family_and_pass(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q = jnp.zeros((1, 2, 256, 64))          # [b, h, t, dh]
    o, lse = jax.eval_shape(
        lambda q: fa.flash_attention_fwd(q, q, q, q_block=128, k_block=128),
        q)
    x = jnp.zeros((1, 128, 2, 64))          # [b, t, h, dh]
    k = jnp.zeros((1, 1024, 2, 64))
    assert fa.bthd_family(128, 128, 2, 64) == "bthd_small"
    assert fa.bthd_family(128, 1024, 2, 64) == "bthd_kblock"

    def bthd(kv):
        out, lse2 = jax.eval_shape(
            lambda x, kv: fa.flash_attention_bthd_fwd(x, kv, kv), x, kv)
        zeros = jnp.zeros(out.shape), jnp.zeros(lse2.shape)
        return (pallas_names(
            lambda x, kv: fa.flash_attention_bthd_fwd(x, kv, kv), x, kv)
            + pallas_names(
                lambda x, kv: fa.flash_attention_bthd_bwd(
                    x, kv, kv, None, None, *zeros, zeros[0]), x, kv))

    got = (
        pallas_names(lambda q: fa.flash_attention_fwd(
            q, q, q, q_block=128, k_block=128), q)
        + pallas_names(lambda q: fa.flash_attention_bwd(
            q, q, q, None, None, jnp.zeros(o.shape), jnp.zeros(lse.shape),
            jnp.zeros(o.shape), q_block=128, k_block=128), q)
        # one head a step: the backward is ONE call (fa.bhtd_bwd_form)
        + pallas_names(lambda q: fa.flash_attention_bwd(
            q, q, q, None, None, jnp.zeros(q.shape),
            jnp.zeros(q.shape[:3] + (1,)), jnp.zeros(q.shape),
            q_block=128, k_block=128), q[:, :1])
        + bthd(k) + bthd(x)
        + pallas_names(lambda s: fa.bthd_dropout_masks(
            1, 128, 128, 2, 64, 0.1, s), jnp.zeros((), jnp.int32)))
    assert got == [
        "attn.bhtd.fwd", "attn.bhtd.bwd_dq", "attn.bhtd.bwd_dkv",
        "attn.bhtd.bwd",
        "attn.bthd_kblock.fwd", "attn.bthd_kblock.bwd",
        "attn.bthd_small.fwd", "attn.bthd_small.bwd",
        "attn.bthd_small.dropout_masks"]
    # every call site of the file is one of them
    src = open(fa.__file__).read()
    assert src.count("pl.pallas_call(") == 9 == len(
        re.findall(r'^ +\w+, name=f?"attn\.', src, re.M))


# --- the executor's spans in a jax.profiler trace ---------------------------


def host_spans(trace_dir):
    """{thread line: [(name, start_ns, duration_ns, stats)]} of the
    spans the program annotated, from the trace's /host:CPU plane."""
    from jax.profiler import ProfileData

    (path,) = [os.path.join(r, f) for r, _, fs in os.walk(trace_dir)
               for f in fs if f.endswith(".xplane.pb")]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("executor.")]
            if evs:
                out[line.name] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def traced_steps(tmp_path, steps=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        # some milliseconds of work a call, so that the microseconds
        # the span machinery itself takes between children stay small
        x = layers.data("x", shape=[256], dtype="float32")
        h = x
        for _ in range(6):
            h = layers.fc(h, 256, act="relu")
        loss = layers.mean(h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((512, 256), np.float32)}
    for _ in range(2):      # compile outside the trace
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    first = exe._step
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(steps):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    return first, host_spans(str(tmp_path))


@pytest.fixture
def telemetry_flags():
    keep = {k: flags.get_flag(k) for k in ("telemetry", "step_phases")}
    yield
    flags.set_flags(keep)


def test_spans_reach_the_profilers_trace_with_telemetry_on(
        tmp_path, telemetry_flags):
    # counters on, step phases off: no call waits for the device
    flags.set_flags({"telemetry": True, "step_phases": False})
    first, by_line = traced_steps(tmp_path, steps=4)
    (events,) = by_line.values()        # one thread dispatched them all
    roots = [e for e in events if e[0] == "executor.run"]
    assert [e[3]["step"] for e in roots] == [first + i for i in range(4)]
    children = ("executor.prepare", "executor.state", "executor.run_step",
                "executor.commit")
    for _, start, dur, _ in roots:
        inside = [e for e in events if e[0] != "executor.run"
                  and start <= e[1] and e[1] + e[2] <= start + dur]
        assert tuple(e[0] for e in inside) == children   # in this order
        # the children do not overlap (how much of the call they tile is
        # a host-clock share: the chip's exec.run_ms_per_call.train and
        # exec.*_ms_per_call read it, not a test beside five workers)
        assert all(a[1] + a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    # the same spans feed the histogram, as before
    hist = monitor.histogram("pt_span_seconds")
    assert hist.count(labels={"span": "executor.run"}) >= 4
    assert hist.count(labels={"span": "executor.commit"}) >= 4


def test_no_span_reaches_the_trace_with_telemetry_off(tmp_path,
                                                    telemetry_flags):
    flags.set_flags({"telemetry": False})
    _, by_line = traced_steps(tmp_path, steps=2)
    assert by_line == {}
    # the off path hands out one shared null context, no generator
    assert monitor.span("executor.run", step=1) is monitor.span("x")


def test_run_steps_has_the_same_span_tree(tmp_path, telemetry_flags):
    flags.set_flags({"telemetry": True, "step_phases": False})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feeds = [{"x": np.ones((2, 8), np.float32)}]
    exe.run_steps(main, feeds, steps=3, fetch_list=[loss], scope=scope)
    first = exe._step
    jax.profiler.start_trace(str(tmp_path))
    try:
        exe.run_steps(main, feeds, steps=3, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    (events,) = host_spans(str(tmp_path)).values()
    assert [e[0] for e in events] == [
        "executor.run_window", "executor.prepare", "executor.state",
        "executor.run_step", "executor.commit"]
    assert events[0][3]["step"] == first


# --- what a lowered op's name yields to the one reader of a device trace -----

@pytest.mark.parametrize("tf_op,want", [
    ("jit(step_fn)/bwd/enc0/ffn/mul_grad/transpose(jvp())/dot_general:",
     ("bwd", "enc0/ffn", "mul_grad")),
    ("jit(step_fn)/fwd/enc0/ffn/mul/dot_general", ("fwd", "enc0/ffn", "mul")),
    ("jit(step_fn)/opt/adam/mul:", ("opt", "", "adam")),
    ("jit(step_fn)/bwd/enc1/ffn/relu_grad/transpose(bwd/enc1/ffn/relu_grad)"
     "/jvp()/select_n:", ("bwd", "enc1/ffn", "relu_grad")),
    ("jit(main)/fwd/loop/while/while/body/fwd/loop/body/elementwise_add/add",
     ("fwd", "loop", "while")),
    ("jit(step_fn)/transpose(jvp())/dot_general:", None),
    ("", None),
])
def test_scope_of_an_op_name(tf_op, want):
    """``<phase>/<scope>/<op>`` of an ``op_name`` as the lowering writes
    it, read by ``perf.spans.parse_scope``: every per-layer number on the
    ledger that names a phase, a layer or a Fluid op comes through it."""
    from perf import spans

    got = spans.parse_scope(tf_op)
    assert (got and (got["phase"], got["scope"], got["op"])) == want
