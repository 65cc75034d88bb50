"""Recomputation by segments (backward.py's module docstring): a builder
marks variables (``layers.checkpoint``), ``append_backward`` appends the
forward ops between two marks again in front of their grad ops, and the
backward pass reads the replay. Held here on the CPU: the gradients do
not move, dropout replays its masks, a Program without marks gets the op
list it always got, the memory ledger's ``saved`` falls to the marks, a
mark in a sub-block is refused and one off the loss's path is ignored and
counted. That XLA keeps the replay apart from the first run is a
compile for the chip: tests/test_recompute_compiles_for_v5e.py."""

import math
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward, flags, layers, monitor
from paddle_tpu.models import granite_hybrid as G

TAG = re.compile(re.escape(backward.RECOMPUTE_TAG) + r"\d+")


@pytest.fixture(autouse=True)
def _telemetry_off():
    flags.set_flags({"telemetry": False})
    yield
    flags.set_flags({"telemetry": False})


def run(main, startup, feed, fetch):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    return [np.asarray(v) for v in exe.run(main, feed=feed,
                                           fetch_list=fetch, scope=scope)]


def loss_and_grads(main, startup, loss, feed):
    """{name: value} of the loss and every parameter's gradient."""
    names = sorted(main._param_grad_map)
    out = run(main, startup, feed,
              [loss] + [main._param_grad_map[n] for n in names])
    return dict(zip(["loss"] + names, out))


# --------------------------------------------------------------------------
# a tiny decoder in float32: the gradients do not move
# --------------------------------------------------------------------------

def tiny_decoder(recompute, explicit=False):
    """``explicit``: the builder marks nothing; every sublayer's output (a
    block's two residual adds) goes to ``append_backward(checkpoints=)``."""
    cfg = G.GraniteHybridConfig(
        vocab_size=50, hidden_size=32, first_layer=4, num_hidden_layers=3,
        shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=8, mamba_chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, recompute=recompute)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        model = G.build(cfg)
        marks = [op.outputs["Out"][0] for op in main.global_block().ops
                 if op.type == "elementwise_add"
                 and op.namescope.startswith("blk")] if explicit else None
        backward.append_backward(model["loss"], checkpoints=marks)
    return main, startup, model["loss"], G.make_batch(cfg, 2, 16, seed=3)


@pytest.mark.parametrize("recompute,explicit", [("layer", False),
                                                ("none", True)],
                         ids=["layers_marked_by_the_builder",
                              "sublayers_by_the_checkpoints_argument"])
def test_a_tiny_decoders_gradients_do_not_move(recompute, explicit):
    want = loss_and_grads(*tiny_decoder("none"))
    main, *rest = tiny_decoder(recompute, explicit)
    got = loss_and_grads(main, *rest)
    barriers = sum(op.type == "recompute_barrier"
                   for op in main.global_block().ops)
    assert barriers == (5 if explicit else 3)
    assert set(got) == set(want) and len(want) > 20
    for name, w in want.items():
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(got[name] - w).max() / scale < 1e-6, name


def test_the_decoders_marks_are_its_layers_inputs():
    main = tiny_decoder("layer")[0]
    assert len(main._checkpoints) == 4
    ops = main.global_block().ops
    barriers = [op for op in ops if op.type == "recompute_barrier"]
    # three segments, a layer each: the tail behind the last mark (the
    # final norm and the head) is not replayed
    assert len(barriers) == 3
    for k, op in zip((2, 1, 0), barriers):
        mark, grad = op.inputs["X"]
        assert mark == main._checkpoints[k] and grad.endswith("@GRAD")
        assert op.outputs["Out"] == [f"{mark}{backward.RECOMPUTE_TAG}{k}",
                                     f"{grad}{backward.RECOMPUTE_TAG}{k}"]
    # a replayed op is a backward op under its first run's name scope
    replayed = [op for op in ops if op.role == "bwd" and op.namescope
                and not op.type.endswith("_grad")]
    assert replayed and {op.namescope.split("/")[0] for op in replayed} == {
        "blk4", "blk5", "blk6"}
    assert all("forward_op_idx" in op.attrs for op in replayed)
    assert {"mamba2_scan", "scaled_dot_product_attention"} <= {
        op.type for op in replayed}


# --------------------------------------------------------------------------
# a tiny transformer with dropout: the same masks
# --------------------------------------------------------------------------

def tiny_transformer(marks, explicit=False, d=16, heads=2, blocks=3,
                     p_drop=0.3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    marked = []
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[8, d], dtype="float32")
        y = layers.data("y", shape=[8, 1], dtype="int64")
        h = layers.fc(x, d, num_flatten_dims=2)
        for i in range(blocks):
            marked.append(h)
            if marks and not explicit:
                layers.checkpoint(h)
            with fluid.name_scope(f"blk{i}"):
                u = layers.layer_norm(h, begin_norm_axis=2)
                q, k, v = (layers.transpose(layers.reshape(
                    layers.fc(u, d, num_flatten_dims=2),
                    [0, 0, heads, d // heads]), [0, 2, 1, 3])
                    for _ in range(3))
                a = layers.scaled_dot_product_attention(
                    q, k, v, 1.0 / math.sqrt(d // heads))
                a = layers.reshape(layers.transpose(a, [0, 2, 1, 3]),
                                   [0, 0, d])
                h = h + layers.dropout(layers.fc(a, d, num_flatten_dims=2),
                                       p_drop)
                u = layers.fc(layers.layer_norm(h, begin_norm_axis=2),
                              4 * d, num_flatten_dims=2, act="relu")
                h = h + layers.dropout(
                    layers.fc(layers.dropout(u, p_drop), d,
                              num_flatten_dims=2), p_drop)
        logits = layers.fc(h, 7, num_flatten_dims=2)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        kw = {"checkpoints": marked} if marks and explicit else {}
        pg = backward.append_backward(loss, **kw)
    r = np.random.RandomState(0)
    feed = {"x": r.randn(4, 8, d).astype("float32"),
            "y": r.randint(0, 7, (4, 8, 1)).astype("int64")}
    return main, startup, loss, feed, pg


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["marked_on_the_program", "checkpoints_argument"])
def test_dropout_replays_its_masks(explicit):
    def grads(marks):
        main, startup, loss, feed, pg = tiny_transformer(marks, explicit)
        out = run(main, startup, feed, [loss] + [g for _, g in pg])
        return main, dict(zip(["loss"] + [p.name for p, _ in pg], out))

    plain, want = grads(False)
    marked, got = grads(True)
    n_drop = lambda prog: sum(op.type == "dropout"
                              for op in prog.global_block().ops)
    # two segments of three dropouts each are made again
    assert n_drop(plain) == 9 and n_drop(marked) == 9 + 6
    for name, w in want.items():
        # the same masks: the same numbers (a sum's order aside)
        np.testing.assert_allclose(got[name], w, rtol=1e-5, atol=1e-7,
                                   err_msg=name)


# --------------------------------------------------------------------------
# a Program without marks gets the op list it always got
# --------------------------------------------------------------------------

def op_list(program):
    return [(op.type, dict(op.inputs), dict(op.outputs),
             {k: v for k, v in op.attrs.items() if k != "forward_op_idx"})
            for op in program.global_block().ops]


def test_a_program_without_marks_gets_the_op_list_it_always_got():
    plain = tiny_transformer(False)[0]
    assert not plain._checkpoints
    types = [op.type for op in plain.global_block().ops]
    assert "recompute_barrier" not in types
    assert not any(TAG.search(n) for op in plain.global_block().ops
                   for n in op.input_arg_names + op.output_arg_names)
    # ... and the marked Program's list is that list with the replay put
    # in: without the barriers and the ops appended again, and with the
    # replay's names read as the first run's, op for op the same
    marked = tiny_transformer(True)[0]
    strip = lambda names: {s: [TAG.sub("", n) for n in ns]
                           for s, ns in names.items()}
    rest = []
    for op in marked.global_block().ops:
        replay = (op.role == "bwd" and not op.type.endswith("_grad")
                  and any(TAG.search(n) for n in op.output_arg_names))
        if not replay:
            rest.append((op.type, strip(op.inputs), strip(op.outputs),
                         {k: v for k, v in op.attrs.items()
                          if k != "forward_op_idx"}))
    assert rest == op_list(plain)
    # a clone carries no mark of its own making
    with fluid.program_guard(plain.clone()):
        pass
    assert plain.clone()._checkpoints == []
    assert marked.clone()._checkpoints == marked._checkpoints
    assert marked.clone(for_test=True)._checkpoints == []


# --------------------------------------------------------------------------
# the memory ledger: what the forward pass keeps falls to the marks
# --------------------------------------------------------------------------

def saved_of(marks):
    flags.set_flags({"telemetry": True})
    main, startup, loss, feed, _ = tiny_transformer(marks)
    run(main, startup, feed, [loss])
    led = monitor.memory_ledgers()[f"program{main._uid}"]
    flags.set_flags({"telemetry": False})
    return led["saved"], main


def test_the_ledgers_saved_falls_to_the_marked_values():
    plain, _ = saved_of(False)
    marked, main = saved_of(True)
    assert marked["bytes"] < 0.6 * plain["bytes"]
    scopes = lambda saved: {r["scope"] for r in saved["rows"]}
    assert scopes(plain) == {"", "blk#"}
    # of the blocks only the tail behind the last mark keeps its values:
    # a third of what three blocks kept
    by_scope = lambda saved, s: sum(r["bytes"] for r in saved["rows"]
                                    if r["scope"] == s)
    # (and the two marks that a block's last op makes, 2048 bytes each)
    assert by_scope(marked, "blk#") == by_scope(plain, "blk#") // 3 + 2 * 2048
    # the marks themselves are kept: [4, 8, 16] float32 each
    kept = [r for r in marked["rows"] if r["shape"] == [4, 8, 16]
            and r["scope"] in ("", "blk#")]
    assert sum(r["count"] for r in kept) >= len(main._checkpoints)


# --------------------------------------------------------------------------
# marks that are refused, and marks that are ignored
# --------------------------------------------------------------------------

def test_a_mark_in_a_sub_block_is_refused():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = main.global_block().create_var(
            name="x", shape=(2, 5, 3), dtype="float32", stop_gradient=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            x_t = rnn.step_input(x)
            h_prev = rnn.memory(shape=(2, 3), init_value=0.0)
            h = layers.tanh(x_t + h_prev)
            with pytest.raises(ValueError, match="sub-block"):
                layers.checkpoint(h)
            rnn.update_memory(h_prev, h)
            rnn.step_output(h)
        loss = layers.mean(rnn())
        with pytest.raises(ValueError, match="sub-block"):
            backward.append_backward(loss, checkpoints=[h.name])
    assert main._checkpoints == []


def test_a_mark_off_the_losss_path_is_ignored_and_counted():
    flags.set_flags({"telemetry": True})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[4], dtype="float32")
        h0 = layers.fc(x, 4)
        h1 = layers.fc(layers.checkpoint(h0), 4, act="tanh")
        h2 = layers.fc(layers.checkpoint(h1), 4, act="tanh")
        side = layers.checkpoint(layers.fc(x, 3))     # nobody reads it
        layers.checkpoint(x)                          # a feed: no op makes it
        loss = layers.mean(layers.fc(h2, 1))
        backward.append_backward(loss)
    rows = lambda name: {
        tuple(sorted(r["labels"].items())): r["value"]
        for r in monitor.snapshot()[name]["values"]
        if r["labels"]["program"] == f"program{main._uid}"}
    prog = ("program", f"program{main._uid}")
    assert rows("pt_backward_checkpoints_total") == {
        (prog, ("used", "false")): 2, (prog, ("used", "true")): 2}
    # one segment (h0 -> h1: mul, elementwise_add, tanh), appended again
    assert rows("pt_backward_recompute_ops_total") == {
        (prog, ("segment", "0")): 3}
    assert side.name in main._checkpoints
    out = run(main, startup, {"x": np.ones((2, 4), np.float32)}, [loss])
    assert np.isfinite(out[0]).all()
