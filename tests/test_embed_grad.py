"""The embedding gradient's kernel (paddle_tpu/parallel/embed_grad.py:
``embed.grad``) through the Pallas interpreter on the CPU, as
tests/test_pair_sum_kernel.py runs ``pairs.sum.*``: against XLA's
scatter-add (the vjp of ``jnp.take``) at the tolerance of a float32
sum's order, on every edge of the ids; what ``embed_grad_tile`` takes
and refuses at the nine cells' calls; and ``lookup_table``'s three grad
forms in a Program (the kernel, the scatter-add, the row-sparse pair)
with the counter that says which."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.parallel import embed_grad as eg

D = 128
TILE = (128, 128)
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(eg, "_INTERPRET", True)
    # a Program's tables here are narrower than the cells'
    monkeypatch.setattr(eg, "_MIN_WIDTH", D)


def scatter_add(g, ids, vocab):
    """XLA's form: the vjp of the forward's ``jnp.take``."""
    take = lambda w: jnp.take(w, ids, axis=0)   # noqa: E731
    zeros = jnp.zeros((vocab, g.shape[-1]), F32)
    return np.asarray(jax.vjp(take, zeros)[1](g.astype(F32))[0])


def held_to_scatter_add(ids, vocab, dtype=F32, tile=TILE, d=D, seed=0):
    ids = jnp.asarray(ids, jnp.int32)
    g = jnp.asarray(np.random.RandomState(seed).randn(ids.size, d), dtype)
    got = np.asarray(eg.embed_grad(g, ids, vocab, tile))
    assert got.shape == (vocab, d) and got.dtype == np.float32
    want = scatter_add(g, ids, vocab)
    # the order of a row's additions differs, nothing else
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
    return got


IDS = {
    "duplicates": lambda r: r.randint(0, 700, 1000),
    "every_id_equal": lambda r: np.full(300, 411),
    "whole_tiles_empty": lambda r: np.r_[r.randint(0, 40, 100),
                                         r.randint(600, 700, 100)],
    "one_id": lambda r: np.array([699]),
    "every_row_once": lambda r: r.permutation(700),
    "not_whole_groups": lambda r: r.randint(0, 700, 333),
}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("case", sorted(IDS))
def test_kernel_matches_the_scatter_add(case, dtype, interpreter):
    ids = IDS[case](np.random.RandomState(1))
    got = held_to_scatter_add(ids, 700, dtype)
    untouched = np.setdiff1d(np.arange(700), ids)
    assert not got[untouched].any()   # zeros written, not left


@pytest.mark.parametrize("vocab", [25008, 18992])
def test_a_vocabulary_that_is_not_whole_tiles(vocab, interpreter):
    """The two claimed cells' tables end inside a tile (48 rows of 128):
    ids on both sides of the last tile's first row and on the table's
    last row."""
    r = np.random.RandomState(2)
    edge = vocab // TILE[0] * TILE[0]
    ids = np.r_[r.randint(0, vocab, 200), edge - 1, edge, vocab - 1,
                vocab - 1]
    held_to_scatter_add(ids, vocab)


@pytest.mark.parametrize("tile", [(256, 128), (512, 128), (256, 256)])
def test_other_tiles(tile, interpreter):
    ids = np.random.RandomState(3).randint(0, 1500, 900)
    held_to_scatter_add(ids, 1500, tile=tile)


def test_float32_rows_are_not_rounded(interpreter):
    """A float32 cotangent keeps all 24 bits: a row that bf16 would
    round to its neighbour comes back exactly, alone in its table row."""
    g = jnp.asarray([[1.0 + 2.0 ** -20] * D, [3.0 - 2.0 ** -18] * D], F32)
    got = np.asarray(eg.embed_grad(g, jnp.asarray([5, 300]), 400, TILE))
    np.testing.assert_array_equal(got[[5, 300]], np.asarray(g))


def test_keys_outside_the_table_add_nothing(interpreter):
    g = jnp.ones((4, D), F32)
    got = np.asarray(eg.embed_grad(g, jnp.asarray([-1, 400, 2 ** 30, 7]),
                                   400, TILE))
    assert got.sum() == D and got[7].sum() == D


def test_the_steps_of_a_call_are_tiles_plus_groups_at_most():
    """``work_items``: every tile once at least, a tile's groups in
    order, dead steps on the last step's blocks."""
    keys = jnp.sort(jnp.asarray(
        np.r_[np.random.RandomState(4).randint(0, 1000, 380), [1024] * 4],
        jnp.int32))
    tile, at, live = (np.asarray(x) for x in eg.work_items(keys, 1000, 256,
                                                           128))
    assert len(tile) == 4 + 3 and set(tile) == {0, 1, 2, 3}
    assert (np.diff(tile) >= 0).all() and (np.diff(at) >= 0).all()
    lo = np.searchsorted(np.asarray(keys), np.arange(4) * 256)
    hi = np.searchsorted(np.asarray(keys), np.arange(1, 5) * 256)
    for t in range(4):   # the live steps of a tile cover its segment
        groups = at[(tile == t) & (live == 1)]
        assert list(groups) == list(range(lo[t] // 128,
                                          -(-hi[t] // 128)))


# (rows a step, rows of the table, width) of the nine cells' calls
CELLS = {
    "phi4flash-train-s4096": (4096, 25008, 2560),
    "smallthinker-train-s16384": (16384, 18992, 2560),
    "olmoe-train-s4096": (8192, 50304, 2048),
    "qwen3next-train-s8192": (8192, 18992, 2048),
    "joyai-train-s4096": (4096, 16160, 2048),
    "nemotron3nano-train-s4096": (4096, 16384, 2688),
    "tbase-train": (32768, 10000, 512),
    "bert-train": (32768, 30522, 768),
    "tbase-train-dp4": (32768, 10000, 512),
}
KERNEL = {"phi4flash-train-s4096", "smallthinker-train-s16384",
          "olmoe-train-s4096", "qwen3next-train-s8192", "joyai-train-s4096",
          "nemotron3nano-train-s4096"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_embed_grad_tile_at_the_cells_calls(cell):
    n, vocab, d = CELLS[cell]
    tile = eg.embed_grad_tile(n, vocab, d, F32, backend="tpu",
                              on_mesh=cell.endswith("dp4"))
    assert tile == (TILE if cell in KERNEL else None)


def test_embed_grad_tile_refuses():
    n, vocab, d = CELLS["phi4flash-train-s4096"]

    def tile(n=n, vocab=vocab, d=d, dtype=F32, backend="tpu",
             on_mesh=False):
        return eg.embed_grad_tile(n, vocab, d, dtype, backend, on_mesh)

    assert tile() == TILE and tile(dtype=BF16) == TILE
    assert tile(on_mesh=True) is None      # a Mosaic call under a mesh
    assert tile(backend="cpu") is None     # off a TPU
    assert tile(d=2560 + 64) is None       # off the 128 lanes
    assert tile(d=512) is None             # the older cells' tables
    assert tile(dtype=jnp.float16) is None
    assert tile(n=0) is None
    assert tile(d=65536) is None           # blocks over the VMEM cap
    # this process: a CPU, no interpreter
    assert eg.embed_grad_tile(n, vocab, d, F32) is None


def table_grad(ids, *, vocab=300, tied=False, twice=False, **embedding):
    """table@GRAD of a Program that squares an embedding of ``ids``, the
    grad ops' types and the dispatch counter's rows."""
    flags.set_flags({"telemetry": True})
    monitor.reset()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("ids", shape=list(ids.shape[1:]), dtype="int64")
        table = fluid.ParamAttr(name="table")
        emb = layers.embedding(x, [vocab, D], param_attr=table, **embedding)
        if twice:
            emb = emb + layers.embedding(x, [vocab, D], param_attr=table,
                                         **embedding)
        loss = layers.reduce_sum(emb * emb)
        if tied:   # the table once more, as a head's weight
            w = main.global_block().var("table")
            loss = loss + layers.reduce_sum(layers.square(w))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fetch = [] if embedding.get("is_sparse") else ["table@GRAD"]
        out = exe.run(main, feed={"ids": ids}, fetch_list=fetch)
    rows = {v["labels"]["impl"]: int(v["value"]) for v in
            monitor.snapshot().get("pt_embedding_grad_dispatch_total",
                                   {}).get("values", [])}
    flags.set_flags({"telemetry": False})
    types = [op.type for op in main.global_block().ops
             if op.type.startswith("lookup_table_")]
    return (out[0] if out else None), types, rows


PROGRAMS = {
    "b_t_ids": dict(ids=(4, 50)),
    "n_1_ids": dict(ids=(200, 1), squeeze=True),
    "padding_idx": dict(ids=(4, 50), padding_idx=7),
    "negative_padding_idx": dict(ids=(4, 50), padding_idx=-1),
    "tied": dict(ids=(4, 50), tied=True),
    "two_lookups": dict(ids=(4, 50), twice=True),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_a_programs_table_gradient_either_way(case, monkeypatch):
    """``lookup_table_grad`` with the kernel and with the scatter-add:
    the same gradient, negative ids (the row vocab + id) among them (an
    id the forward does not find reads NaN there, and so does the loss);
    a tied table's two gradients and two lookups of one table sum; the
    counter names the form."""
    kw = dict(PROGRAMS[case])
    shape, squeeze = kw.pop("ids"), kw.pop("squeeze", False)
    ids = np.random.RandomState(5).randint(-3, 300, shape).astype(np.int64)
    if squeeze:   # the reference's column ids: the op squeezes by itself
        monkeypatch.setattr(
            layers.nn.LayerHelper, "append_op",
            lambda self, type, inputs, outputs, attrs: self.main_program
            .current_block().append_op(type, inputs=inputs, outputs=outputs,
                                       attrs={k: v for k, v in attrs.items()
                                              if k != "squeeze_last"}))
    calls = 2 if kw.get("twice") else 1
    want, types, rows = table_grad(ids, **kw)
    assert types == ["lookup_table_grad"] * calls and rows == {"xla": calls}
    monkeypatch.setattr(eg, "_INTERPRET", True)
    monkeypatch.setattr(eg, "_MIN_WIDTH", D)
    got, types, rows = table_grad(ids, **kw)
    assert types == ["lookup_table_grad"] * calls
    assert rows == {"kernel": calls}
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())
    assert np.abs(want).max() > 0


def test_the_rule_takes_a_bf16_cotangent_as_it_arrives(interpreter):
    """Under AMP a cotangent can arrive as bf16: the kernel adds its
    rows as they are, where the scatter-add casts them to float32
    first; the gradient is W's dtype either way."""
    from paddle_tpu.core.registry import get_op_def

    r = np.random.RandomState(8)
    ids = jnp.asarray(r.randint(-3, 300, (4, 50)), jnp.int32)
    g = jnp.asarray(r.randn(4, 50, D), BF16)
    w = jnp.zeros((300, D), F32)
    (got,) = get_op_def("lookup_table_grad").compute(
        {"W": [w], "Ids": [ids], "GRAD::Out": [g]},
        {"squeeze_last": False, "padding_idx": 7})["GRAD::W"]
    assert got.dtype == F32
    want = scatter_add(g.reshape(-1, D), ids.reshape(-1), 300).copy()
    want[7] = 0   # the padding row
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_a_sparse_table_keeps_its_row_sparse_pair(interpreter):
    ids = np.random.RandomState(6).randint(0, 300, (4, 50)).astype(np.int64)
    _, types, rows = table_grad(ids, is_sparse=True)
    assert types == ["lookup_table_sparse_grad"] and rows == {}


def test_a_row_sharded_lookup_keeps_the_generic_grad_op(interpreter):
    """``is_distributed``: the generic emitter's op (W, Ids, Out and the
    forward's slots in its attrs), which takes the vjp whatever
    ``embed_grad_tile`` would say."""
    ids = np.random.RandomState(7).randint(0, 300, (4, 50)).astype(np.int64)
    want, _, _ = table_grad(ids)
    got, types, rows = table_grad(ids, is_distributed=True)
    assert types == ["lookup_table_grad"] and rows == {"xla": 1}
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
