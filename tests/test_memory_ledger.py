"""The memory ledger of a lowering (PR 69): ``monitor.memory_ledgers()``
holds, for every program lowered with telemetry on, what its state
weighs (parameters against optimizer state), what one step's feeds
weigh, every value its forward pass keeps for its backward pass (by
scope, op and slot, as traced, plain and padded to the chip's tiles)
and the peak of a liveness walk over its ops. The first test writes a
whole ledger out by hand; the others hold one rule each. CPU-only,
non-slow."""

import copy

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, flags, layers, monitor
from paddle_tpu.core import lowering
from paddle_tpu.core.registry import has_op

GAUGE = "pt_program_memory_bytes"
KINDS = ("param", "optimizer", "feed", "saved", "saved_padding",
         "walk_peak")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False})
    yield
    flags.set_flags({"telemetry": False})


def two_layers(optimizer=True):
    """x [b, 8] -> two blocks of fc(16, relu) + dropout -> mean, Adam."""
    main, startup = fluid.Program(), fluid.Program()
    # (names from a generator of its own: fc_0, dropout_0 whatever the
    # worker built before)
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        h = layers.data("x", shape=[8], dtype="float32")
        for i in range(2):
            with fluid.name_scope(f"blk{i}"):
                h = layers.dropout(layers.fc(h, 16, act="relu"), 0.1)
        loss = layers.mean(h)
        evalp = main.clone(for_test=True)
        if optimizer:
            fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, evalp, loss


def run_once(main, startup, loss, feed=None, exe=None, scope=None):
    exe = exe or fluid.Executor(fluid.CPUPlace())
    scope = scope or fluid.Scope()
    if startup is not None:
        exe.run(startup, scope=scope)
    exe.run(main, feed=feed or {"x": np.ones((4, 8), np.float32)},
            fetch_list=[loss], scope=scope)
    return exe, scope


def ledger_of(program):
    return monitor.memory_ledgers()[f"program{program._uid}"]


def gauge_rows(program):
    return {c["labels"]["kind"]: c["value"]
            for c in monitor.snapshot()[GAUGE]["values"]
            if c["labels"]["program"] == f"program{program._uid}"}


# --------------------------------------------------------------------------
# one ledger, by hand
# --------------------------------------------------------------------------

def test_a_two_layer_programs_ledger_written_out_by_hand():
    """Batch 4. Every float32 [4, 16] is 256 bytes and one (8, 128)
    tile of 4096; a dropout mask is uint8 [4, 16], 64 bytes and one
    (32, 128) tile of 4096; the fed x [4, 8] is 128 bytes and 4096; the
    loss is a scalar of 4.

    The 23 ops: 0-3 blk0's mul, elementwise_add, relu, dropout; 4-7
    blk1's; 8 mean; 9 fill_any_like (the loss's gradient); 10 mean_grad;
    11-14 blk1's dropout_grad, relu_grad, elementwise_add_grad,
    mul_grad; 15-18 blk0's; 19-22 four adam ops. Each generic grad op
    reads its forward's inputs and outputs, so every forward value
    crosses: five a block, x (mul_grad reads it) and the loss.

    The walk: at op 11 (blk1's dropout_grad) all ten block values, the
    loss, the gradient it reads and the one it writes are alive, 12
    tiles and 4 bytes; from op 12 on two forward values die for each
    gradient made."""
    flags.set_flags({"telemetry": True})
    main, startup, _, loss = two_layers()
    run_once(main, startup, loss)
    led = ledger_of(main)
    monitor.validate_memory_ledger(led)
    assert (led["n_ops"], led["amp"], led["has_backward"]) == (
        23, False, True)

    # parameters: w [8, 16], b [16], w [16, 16], b [16], float32
    assert led["state"]["param"] == 4 * (128 + 16 + 256 + 16) == 1664
    # two moments a parameter, two powers of [1] each, one rate of [1]
    assert led["state"]["optimizer"] == 2 * 1664 + 4 * 2 * 4 + 4 == 3364
    assert led["state"]["arrays"] == 4 * 5 + 1
    # a [16] or [1] is 1024 elements on the chip, [8, 16] one tile,
    # [16, 16] two
    tile = 4096
    padded_params = tile + tile + 2 * tile + tile
    assert led["state"]["padded_bytes"] == 3 * padded_params \
        + 8 * tile + tile
    assert led["feed"] == {"bytes": 128, "padded_bytes": tile, "arrays": 1}

    saved = led["saved"]
    assert saved["values"] == 2 * 5 + 1 + 1
    assert saved["bytes"] == 2 * (4 * 256 + 64) + 128 + 4 == 2308
    assert saved["padded_bytes"] == 11 * tile + 4
    block_row = {"scope": "blk#", "shape": [4, 16], "count": 2,
                 "dtype": "float32", "slot": "Out", "bytes": 512,
                 "padded_bytes": 2 * tile}
    assert saved["rows"] == [
        # largest first; equal rows by scope, op, slot
        dict(block_row, op="dropout", slot="Mask", dtype="uint8",
             bytes=128),
        dict(block_row, op="dropout"),
        dict(block_row, op="elementwise_add"),
        dict(block_row, op="mul"),
        dict(block_row, op="relu"),
        {"scope": "", "op": "feed", "slot": "x", "shape": [4, 8],
         "dtype": "float32", "count": 1, "bytes": 128,
         "padded_bytes": tile},
        {"scope": "", "op": "mean", "slot": "Out", "shape": [],
         "dtype": "float32", "count": 1, "bytes": 4, "padded_bytes": 4}]

    walk = led["walk_peak"]
    assert (walk["index"], walk["role"], walk["scope"], walk["op"]) == (
        11, "bwd", "blk1", "dropout_grad")
    assert walk["bytes"] == led["state"]["padded_bytes"] + tile \
        + 12 * tile + 4
    # the five largest alive there: equal tiles, so the oldest
    assert [(r["name"], r["scope"], r["op"], r["slot"])
            for r in walk["alive"]] == [
        ("fc_0.tmp_0", "blk0", "mul", "Out"),
        ("fc_0.tmp_1", "blk0", "elementwise_add", "Out"),
        ("fc_0.tmp_2", "blk0", "relu", "Out"),
        ("dropout_0.tmp_0", "blk0", "dropout", "Out"),
        ("dropout_0.tmp_1", "blk0", "dropout", "Mask")]

    assert gauge_rows(main) == {
        "param": 1664, "optimizer": 3364, "feed": 128,
        "saved": 11 * tile + 4, "saved_padding": 11 * tile + 4 - 2308,
        "walk_peak": walk["bytes"]}


# --------------------------------------------------------------------------
# the padding rule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dtype,want", [
    # the forward's logsumexp as a column (PR 60): 512 B a position for 4
    ((2, 16, 4096, 1), "float32", 2 * 16 * 4096 * 128 * 4),
    # experts' width off the lanes: 1856 -> 1920
    ((4096, 1856), "float32", 4096 * 1920 * 4),
    # BTHD-small's lse (PERF.md section 7 (27c)): sixteen-fold
    ((128, 256, 8), "float32", 128 * 256 * 128 * 4),
    # 16-bit rows pair up in a sublane: second-minor to 16
    ((3, 8, 128), "bfloat16", 3 * 16 * 128 * 2),
    ((3, 16, 128), "bfloat16", 3 * 16 * 128 * 2),
    # 8-bit: second-minor to 32
    ((40, 128), "uint8", 64 * 128),
    ((8, 128), "float32", 8 * 128 * 4),
    # rank 1: whole tiles of 1024 elements
    ((1,), "float32", 4096),
    ((1024,), "float32", 4096),
    ((1025,), "bfloat16", 2048 * 2),
    # a scalar is its element; nothing is nothing
    ((), "float32", 4),
    ((0, 128), "float32", 0),
])
def test_padded_bytes_under_the_chips_default_tiling(shape, dtype, want):
    assert lowering.tile_padded_bytes(shape, dtype) == want


# --------------------------------------------------------------------------
# as traced, not as declared
# --------------------------------------------------------------------------

def test_under_amp_a_bf16_value_counts_two_bytes_though_declared_float32():
    flags.set_flags({"telemetry": True})
    main, startup, _, loss = two_layers()
    amp.enable_amp(main)
    declared = main.global_block().var("fc_0.tmp_0")
    assert str(declared.dtype).endswith("float32")
    run_once(main, startup, loss)
    led = ledger_of(main)
    assert led["amp"]
    mul = next(r for r in led["saved"]["rows"]
               if (r["scope"], r["op"]) == ("blk#", "mul"))
    # mul's output stays bf16 under AMP: [4, 16] x 2 bytes, twice; on
    # the chip 16 rows of 128 lanes each
    assert (mul["dtype"], mul["bytes"], mul["padded_bytes"]) == (
        "bfloat16", 2 * 4 * 16 * 2, 2 * 16 * 128 * 2)
    # the master weights stay float32
    assert led["state"]["param"] == 1664


# --------------------------------------------------------------------------
# which values cross
# --------------------------------------------------------------------------

def gathered(monkeypatch):
    """The ValueLedger of every lowering, as build_memory_ledger saw it."""
    seen = []
    real = lowering.build_memory_ledger

    def spy(ledger, *args):
        seen.append(ledger)
        return real(ledger, *args)

    monkeypatch.setattr(lowering, "build_memory_ledger", spy)
    return seen


def crossing(ledger):
    return {v.name for v in ledger.values
            if v.role in ("fwd", "feed") and v.last_role in ("bwd", "opt")
            and v.kind != "state"}


def test_a_generic_grad_op_keeps_the_forward_names_it_reads_and_no_residual(
        monkeypatch):
    """tanh and elementwise_mul have no grad op of their own: the
    generic one traces the forward again (core/autodiff) and reads the
    forward's inputs and outputs from the environment. What crosses is
    exactly those names; what the second trace makes inside the grad
    op's compute is no variable of the Program."""
    flags.set_flags({"telemetry": True})
    seen = gathered(monkeypatch)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        h = layers.fc(x, 8, bias_attr=False)
        t = layers.tanh(h)
        loss = layers.mean(layers.elementwise_mul(t, t))
        fluid.optimizer.SGD(0.1).minimize(loss)
    ops = main.global_block().ops
    assert not has_op("tanh_grad") and not has_op("elementwise_mul_grad")
    run_once(main, startup, loss)
    written_fwd = {n for op in ops if op.role == "fwd"
                   for n in op.output_arg_names} | {"x"}
    read_bwd = {n for op in ops if op.role != "fwd"
                for n in op.input_arg_names if n}
    step = seen[-1]
    assert crossing(step) == written_fwd & read_bwd
    assert {h.name, t.name, "x", loss.name} <= crossing(step)
    # and every value of the ledger is a name of the Program
    names = {n for op in ops for n in op.output_arg_names} | {"x"}
    assert {v.name for v in step.values if v.kind != "state"} <= names


def test_a_control_flow_ops_sub_block_adds_no_rows(monkeypatch):
    """The branches of a cond lower through exec_ops again, nested in
    the cond op's compute: their values are the op's business. The
    ledger holds the op's output and none of theirs."""
    flags.set_flags({"telemetry": True})
    seen = gathered(monkeypatch)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        h = layers.fc(x, 8, bias_attr=False)
        pred = layers.less_than(layers.mean(h),
                                layers.fill_constant([1], "float32", 1e9))
        out = layers.cond(pred, lambda: layers.tanh(layers.scale(h, 2.0)),
                          lambda: layers.scale(h, 3.0))
        loss = layers.mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    inner = {n for b in main.blocks[1:] for op in b.ops
             for n in op.output_arg_names}
    assert inner   # the branches do write variables of their own
    run_once(main, startup, loss)
    step = seen[-1]
    assert ledger_of(main)["n_ops"] == len(main.global_block().ops)
    assert not inner & {v.name for v in step.values}
    assert out.name in {v.name for v in step.values}
    assert out.name in crossing(step)


@pytest.mark.parametrize("dead", [False, True])
def test_a_value_nothing_reads_takes_no_room_in_the_walk(dead):
    """An output nothing reads and nothing fetches (a softmax kept for a
    grad op that makes it again) is dead code to XLA: the walk's peak
    is the program's without the op, whatever the value weighs."""
    flags.set_flags({"telemetry": True})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[8], dtype="float32")
        h = layers.fc(x, 16, bias_attr=False)
        if dead:
            layers.expand(h, expand_times=[1, 64])
        loss = layers.mean(layers.tanh(h))
    run_once(main, startup, loss)
    walk = ledger_of(main)["walk_peak"]
    # state w [8, 16] and feed x [4, 8] one tile each; at tanh (or at
    # mean) two [4, 16] tiles, or one and the scalar, are alive
    assert walk["bytes"] == 4 * 4096
    assert "expand" not in {r["op"] for r in walk["alive"]}
    assert ledger_of(main)["n_ops"] == 3 + dead


def test_the_eval_clone_has_no_backward_and_keeps_nothing():
    flags.set_flags({"telemetry": True})
    main, startup, evalp, loss = two_layers()
    exe, scope = run_once(main, startup, loss)
    run_once(evalp, None, loss, exe=exe, scope=scope)
    led = ledger_of(evalp)
    monitor.validate_memory_ledger(led)
    assert not led["has_backward"]
    assert led["saved"] == {"bytes": 0, "padded_bytes": 0, "values": 0,
                            "rows": []}
    # only parameters are its state: no op of it is an optimizer's
    assert (led["state"]["param"], led["state"]["optimizer"]) == (1664, 0)
    assert ledger_of(main)["has_backward"]


def test_a_second_lowering_of_a_program_replaces_its_record():
    flags.set_flags({"telemetry": True})
    main, startup, _, loss = two_layers()
    exe, scope = run_once(main, startup, loss)
    first = ledger_of(main)
    run_once(main, None, loss, feed={"x": np.ones((12, 8), np.float32)},
             exe=exe, scope=scope)
    second = ledger_of(main)
    assert list(monitor.memory_ledgers()).count(
        f"program{main._uid}") == 1
    assert first["feed"]["bytes"] == 4 * 8 * 4
    assert second["feed"]["bytes"] == 12 * 8 * 4
    assert second["saved"]["bytes"] > first["saved"]["bytes"]
    assert gauge_rows(main)["feed"] == 12 * 8 * 4
    assert set(gauge_rows(main)) == set(KINDS)


# --------------------------------------------------------------------------
# telemetry off
# --------------------------------------------------------------------------

class CountingLedger(lowering.ValueLedger):
    made = noted = 0

    def __init__(self, *args):
        type(self).made += 1
        super().__init__(*args)

    def note(self, *args):
        type(self).noted += 1
        super().note(*args)


def test_with_telemetry_off_nothing_is_gathered_and_nothing_is_kept(
        monkeypatch):
    """The gathering hook is what exec_ops calls an op under its
    ``timed`` gate: off, no ledger is made and not one op is noted; on,
    every op of the block is, once."""
    monkeypatch.setattr(lowering, "ValueLedger", CountingLedger)
    monkeypatch.setattr(CountingLedger, "made", 0)
    monkeypatch.setattr(CountingLedger, "noted", 0)
    assert not monitor.enabled()
    main, startup, _, loss = two_layers()
    run_once(main, startup, loss)
    assert (CountingLedger.made, CountingLedger.noted) == (0, 0)
    assert monitor.memory_ledgers() == {}
    assert monitor.snapshot()[GAUGE]["values"] == []

    flags.set_flags({"telemetry": True})
    main, startup, _, loss = two_layers()
    n_startup = len(startup.global_block().ops)
    run_once(main, startup, loss)
    assert CountingLedger.made == 2     # the startup program's, the step's
    assert CountingLedger.noted == n_startup + 23


def test_a_ledger_handed_to_exec_ops_outside_a_lowering_is_left_alone():
    """Build-time shape inference runs op rules through exec_ops under
    jax.eval_shape: no block is being lowered, ``timed`` is False and a
    ledger, were one handed in, hears nothing."""
    flags.set_flags({"telemetry": True})
    main, _, _, _ = two_layers(optimizer=False)
    ops = main.global_block().ops[:1]
    ledger = CountingLedger({}, {})
    noted = CountingLedger.noted
    env = {"x": np.ones((4, 8), np.float32),
           "fc_0.w_0": np.ones((8, 16), np.float32)}
    lowering.exec_ops(ops, env, ledger=ledger)
    assert "fc_0.tmp_0" in env and CountingLedger.noted == noted


# --------------------------------------------------------------------------
# the schema
# --------------------------------------------------------------------------

@pytest.fixture
def a_ledger():
    flags.set_flags({"telemetry": True})
    main, startup, _, loss = two_layers()
    run_once(main, startup, loss)
    led = ledger_of(main)
    monitor.validate_memory_ledger(led)
    return led


def _without(path):
    def edit(led):
        part = led
        for key in path[:-1]:
            part = part[key]
        del part[path[-1]]
    return edit


def _with(path, value):
    def edit(led):
        part = led
        for key in path[:-1]:
            part = part[key]
        part[path[-1]] = value
    return edit


@pytest.mark.parametrize("edit,says", [
    (_without(("has_backward",)), "missing field 'has_backward'"),
    (_without(("saved",)), "missing field 'saved'"),
    (_with(("has_backward",), 1), "field 'has_backward' has type int"),
    (_with(("state",), [1, 2]), "field 'state' has type list"),
    (_with(("v",), 2), "schema v2 != v1"),
    (_with(("extra",), 0), "unknown fields ['extra']"),
    (_without(("state", "optimizer")), "memory ledger 'state' must be"),
    (_with(("feed", "bytes"), 1.5), "field 'bytes' has type float"),
    (_with(("saved", "values"), True), "field 'values' has type bool"),
    (_with(("walk_peak", "op"), None), "field 'op' has type NoneType"),
    (_without(("saved", "rows", 0, "padded_bytes")),
     "saved row must be"),
    (_with(("saved", "rows", 0, "shape"), "4x16"),
     "field 'shape' has type str"),
    (_without(("walk_peak", "alive", 0, "name")), "alive row must be"),
])
def test_validate_refuses_a_missing_or_mistyped_field(a_ledger, edit, says):
    bad = copy.deepcopy(a_ledger)
    edit(bad)
    with pytest.raises(ValueError) as err:
        monitor.validate_memory_ledger(bad)
    assert says in str(err.value)


def test_every_field_of_the_schema_is_documented_and_recorded(a_ledger):
    assert set(a_ledger) == set(monitor.MEMORY_LEDGER_FIELDS)
    for name, (_types, required, doc) in \
            monitor.MEMORY_LEDGER_FIELDS.items():
        assert required and doc.strip(), name
    # reset() is test isolation for the records as for the metrics
    monitor.reset()
    assert monitor.memory_ledgers() == {}
    assert monitor.snapshot()[GAUGE]["values"] == []
