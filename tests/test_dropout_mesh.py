"""The dropout op's random words under a data-parallel mesh: each shard
draws its own rows (ops/nn_ops._draw_bits), one device draws as before.

The draw is XLA's RngBitGenerator (prng_impl "rbg", the TPU default),
which the SPMD partitioner cannot split: a plain jax.random.bits under
a mesh makes every device generate the GLOBAL tensor. The CPU backend
expands the instruction away when it compiles, so the shapes are read
from the lowered StableHLO.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import backward, flags, layers, monitor, parallel, unique_name
from paddle_tpu.core import interp, lowering
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import nn_ops
from paddle_tpu.parallel.strategy import pipeline_rules

P_DROP = 0.1
THRESHOLD = min(round((1.0 - P_DROP) * 65536.0), 65535)


@pytest.fixture(autouse=True)
def rbg():
    flags.set_flags({"prng_impl": "rbg"})
    yield
    flags.set_flags({"prng_impl": "auto", "telemetry": False})


def drawn_shapes(text):
    """Result shapes (rank >= 1) of the rng_bit_generators in lowered
    StableHLO; the scalar ones are key derivations and kernel seeds."""
    shapes = re.findall(
        r"rng_bit_generator.*-> \(tensor<2xui64>, tensor<([0-9x]+)xui\d+>\)",
        text)
    return [tuple(int(d) for d in s.split("x")) for s in shapes]


def transformer(dropout=P_DROP, n_layer=1, scan=False):
    cfg = T.TransformerConfig(
        src_vocab_size=200, trg_vocab_size=200, d_model=32, d_inner=64,
        n_head=2, n_layer=n_layer, max_length=20, dropout=dropout)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), fluid.program_guard(main, startup):
        model = (T.build_scan if scan else T.build)(cfg)
        fluid.optimizer.SGD(0.05).minimize(model["loss"])
    return cfg, main, startup, model["loss"]


def dropout_program(shape, perm=None):
    """Out, Mask and dOut/dX of one dropout op over a fed ``x`` (over
    its transpose by ``perm``, where given)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=list(shape[1:]), dtype="float32")
        x.stop_gradient = False
        out = layers.dropout(layers.transpose(x, perm) if perm else x,
                             P_DROP,
                             dropout_implementation="upscale_in_train")
        loss = layers.reduce_sum(out)
        (dx,) = backward.gradients([loss], [x])
    (op,) = [o for o in main.global_block().ops if o.type == "dropout"]
    return main, [out.name, op.outputs["Mask"][0], dx.name]


def run_dropout(shape, devices=None, data_axis="data", perm=None):
    main, fetch = dropout_program(shape, perm)
    prog = main
    if devices:
        mesh = Mesh(np.asarray(jax.devices()[:devices]), ("data",))
        prog = fluid.CompiledProgram(main).with_strategy(
            parallel.DistributedStrategy(mesh, data_axis=data_axis))
    exe = fluid.Executor(fluid.CPUPlace())
    x = np.ones(shape, np.float32)
    return [np.asarray(v) for v in exe.run(
        prog, feed={"x": x}, fetch_list=fetch, scope=fluid.Scope())]


# --- (a) the waste, and that it is gone --------------------------------


def test_every_draw_of_a_data_parallel_step_has_the_shards_rows():
    b, n = 8, 4
    cfg, main, startup, loss = transformer()
    feed = T.make_batch(cfg, b, 16, 16, seed=3)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, devices=jax.devices()[:n])
    lowered = lowering.lower_block(main, 0, sorted(feed), [loss.name])
    fn = fluid.Executor._jit_for(lowered, prog)
    with interp.spmd_ctx_scope(prog._strategy):
        text = fn.lower(
            exe._gather_state(scope, lowered),
            {k: np.asarray(v) for k, v in feed.items()},
            exe._base_key_for(main), np.uint32(0)).as_text()
    shapes = drawn_shapes(text)
    # one layer pair: 2 embedding, 5 residual, 2 FFN-inner dropouts
    assert len(shapes) == 9
    assert {s[0] for s in shapes} == {b // n}, shapes
    assert {s[1:] for s in shapes} == {(16, 32), (16, 64)}

    # the parent's graph: the same draw left to the partitioner
    mesh = prog.mesh
    plain = jax.jit(
        lambda key: jax.random.bits(key, (b, 16, 32), jnp.uint16),
        in_shardings=NamedSharding(mesh, P()),
        out_shardings=NamedSharding(mesh, P("data")))
    assert drawn_shapes(plain.lower(
        jax.random.key(0, impl="rbg")).as_text()) == [(b, 16, 32)]


# --- (b) the masks: per shard, at the keep rate, read by the backward ---


def test_shards_never_share_a_mask_and_the_backward_reads_the_forwards():
    n, rows = 4, 64
    out, mask, dx = run_dropout((n * rows, 1024), devices=n)
    shards = mask.reshape(n, rows, 1024)
    for i in range(n):
        for j in range(i + 1, n):
            # independent masks agree on p^2 + (1-p)^2 = 82% of words
            assert 0.80 < (shards[i] == shards[j]).mean() < 0.84, (i, j)
        assert abs(shards[i].mean() - (1 - P_DROP)) < 0.01
    assert abs(mask.mean() - (1 - P_DROP)) < 0.01
    kept = np.float32(1.0) / np.float32(1.0 - P_DROP)
    np.testing.assert_array_equal(out, np.where(mask, kept, 0))
    np.testing.assert_array_equal(dx, out)  # x is ones: dOut/dX == Out
    assert ((dx == 0) == (mask == 0)).all()


# --- (c) one device: the stream and the jaxpr are the parent's ----------


def test_one_device_draws_the_plain_stream_with_no_shard_map():
    shape = (8, 16, 32)
    key = jax.random.key(5, impl="rbg")
    x = jnp.ones(shape, jnp.float32)
    attrs = {"dropout_prob": P_DROP,
             "dropout_implementation": "upscale_in_train"}

    def op(k):
        return nn_ops._dropout({"X": [x]}, attrs, rng=k)["Mask"][0]

    want = jax.random.bits(key, shape, jnp.uint16) < jnp.uint16(THRESHOLD)
    np.testing.assert_array_equal(np.asarray(op(key)),
                                  np.asarray(want).astype(np.uint8))
    assert "shard_map" not in str(jax.make_jaxpr(op)(key))
    # and through the executor: a one-device program's step
    cfg, main, startup, loss = transformer()
    feed = T.make_batch(cfg, 8, 16, 16, seed=3)
    lowered = lowering.lower_block(main, 0, sorted(feed), [loss.name])
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    fn = fluid.Executor._jit_for(lowered, None)
    jaxpr = jax.make_jaxpr(fn)(
        exe._gather_state(scope, lowered),
        {k: np.asarray(v) for k, v in feed.items()},
        exe._base_key_for(main), np.uint32(0))
    assert "shard_map" not in str(jaxpr)


# --- (d) what cannot split runs as before and is counted as repeated ----


def counts_of(fn):
    flags.set_flags({"telemetry": True})
    before = nn_ops.rng_draw_counts()
    result = fn()
    after = nn_ops.rng_draw_counts()
    flags.set_flags({"telemetry": False})
    return result, {k: v - before.get(k, 0) for k, v in after.items()
                    if v != before.get(k, 0)}


@pytest.mark.parametrize("shape,perm,devices,data_axis,row", [
    # a leading dim of 6 over 4 shards: the batch, transposed away
    ((8, 6, 64), [1, 0, 2], 4, "data", "dropout replicated_over=data"),
    ((8, 256), None, 4, None, "dropout replicated_over=data"),  # no data axis
    ((8, 256), None, 4, "data", "dropout sharded_over=data"),
    ((8, 256), None, None, None, "dropout"),                    # one device
])
def test_a_draw_that_cannot_split_is_counted_as_repeated(
        shape, perm, devices, data_axis, row):
    (_, mask, _), counts = counts_of(
        lambda: run_dropout(shape, devices, data_axis, perm))
    assert counts == {row: 1}
    assert mask.shape == (tuple(shape[i] for i in perm) if perm else shape)
    assert abs(mask.mean() - (1 - P_DROP)) < 0.03


def test_dropout_inside_a_gpipe_stage_lowers_and_is_counted():
    """dp x pp with dropout > 0: inside a stage the data axes are manual
    already, so the op draws its (local) shape on the stage's key, the
    same on every data rank; the embeddings' dropouts sit outside the
    pipeline and split over data."""
    cfg, main, startup, loss = transformer(n_layer=2, scan=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    mesh = parallel.create_mesh({"data": 2, "pipe": 2},
                                devices=jax.devices()[:4])
    strategy = parallel.DistributedStrategy(
        mesh, data_axis="data", rules=pipeline_rules("pipe"),
        pipe_axis="pipe", pipe_micro=2)
    prog = fluid.CompiledProgram(main).with_strategy(strategy)

    def two_steps():
        return [float(exe.run(prog, feed=T.make_batch(cfg, 8, 16, 16, seed=s),
                              fetch_list=[loss], scope=scope)[0])
                for s in range(2)]

    losses, counts = counts_of(two_steps)
    assert np.isfinite(losses).all()
    assert set(counts) == {"dropout replicated_over=data",
                           "dropout sharded_over=data replicated_over=pipe"}
    assert counts["dropout sharded_over=data replicated_over=pipe"] == 2


# --- (e) the counter follows the telemetry flag --------------------------


def test_rng_draw_counts_only_with_telemetry_on():
    flags.set_flags({"telemetry": False})
    before = nn_ops.rng_draw_counts()
    run_dropout((8, 128), devices=4)
    assert nn_ops.rng_draw_counts() == before
    _, counts = counts_of(lambda: run_dropout((8, 128), devices=4))
    assert counts == {"dropout sharded_over=data": 1}
    rows = monitor.snapshot()["pt_rng_draw_total"]["values"]
    assert {"op", "sharded_over", "replicated_over"} == set(rows[0]["labels"])
