"""What granite-4.0-h-micro's cell brings to the chip compiles for a TPU
v5e on this CPU-only machine, in the way of
tests/test_attention_compiles_for_v5e.py (one more file, so that one
more worker loads libtpu): a marked Program's step keeps its replay
apart from the first run (XLA's CPU pipeline merges the two, the TPU's
may not: the compiled step's temporaries say which), the
``mamba2.chunk.*`` kernels at ONE group of 64 heads x 16,384 positions in
head blocks, and the ONE backward attention call at 32 / 8 heads of 64 x
16,384, whose float32 rows take whole lane tiles of VMEM. Nothing runs,
so this says nothing about results or times."""

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import lowering
from paddle_tpu.executor import Executor
from paddle_tpu.parallel import flash_attention as fa
from paddle_tpu.parallel import mamba2_scan as K

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    _holds_the_calls, one_chip, real_kernels)


def mlp_blocks(marks, n=8, d=256, f=1024):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        h = layers.fc(layers.data("x", shape=[d], dtype="float32"), d)
        for i in range(n):
            if marks:
                layers.checkpoint(h)
            with fluid.name_scope(f"blk{i}"):
                h = h + layers.fc(layers.fc(h, f, act="tanh"), d)
        loss = layers.mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, loss


def compiled_step(main, loss, chip, rows=16384, d=256):
    low = lowering.lower_block(main, 0, ("x",), (loss.name,))
    block = main.global_block()

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    state = {n: aval(block._find_var_recursive(n).shape,
                     block._find_var_recursive(n).dtype)
             for n in low.state_in_names}
    return Executor._jit_for(low, None).lower(
        state, {"x": aval((rows, d), "float32")}, aval((2,), "uint32"),
        aval((), "uint32")).compile()


def test_the_compiled_steps_temporaries_fall(one_chip, real_kernels):
    """Eight blocks of 256 -> 1024 -> 256 over 16,384 rows, float32: a
    block keeps [16384, 1024] + [16384, 256] = 84 MB for its backward
    pass, eight of them 640 MB (at 4096 rows the compiler finds room for
    all of it off the HBM and both steps read next to nothing); with
    every block's input marked the step holds the marks (17 MB each)
    and ONE block's values at a time: 159 MB. Were the replay merged
    with the first run, or hoisted in front of the backward pass, the
    temporaries would be the unmarked step's."""
    plain = compiled_step(*mlp_blocks(False), one_chip)
    marked = compiled_step(*mlp_blocks(True), one_chip)
    temps = lambda c: c.memory_analysis().temp_size_in_bytes
    assert temps(plain) > 500e6, temps(plain)
    assert temps(marked) < 0.4 * temps(plain), (temps(marked), temps(plain))
    # a replayed block's tanh is made twice (a grad op's own re-trace of
    # its forward merges with the replay, not with the first run)
    made = lambda c: c.as_text().count(" tanh(")
    assert made(marked) > made(plain) >= 8, (made(marked), made(plain))


def test_one_group_of_64_heads_compiles_in_head_blocks(one_chip,
                                                       real_kernels,
                                                       monkeypatch):
    """Granite-4.0-H's call: [1, 16384, 64 x 64] over ONE group's B and C
    [1, 16384, 128]. Eight head blocks of 4 pairs walk the group, each
    writes its own float32 dB and dC."""
    monkeypatch.setattr(K, "kernels_enabled", lambda: True)
    b, t, heads, groups = 1, 16384, 64, 1
    bf = jnp.bfloat16
    tile = K.mamba2_tile(t, heads, groups, K.HEAD_DIM, K.STATE, K.CHUNK, bf,
                         backend="tpu", on_mesh=False)
    assert tile == (4, 8)

    def arg(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def both(x, dt, a, bm, cm, d, dy):
        y, states = K.mamba2_scan_fwd(x, dt, a, bm, cm, d, tile)
        return y, K.mamba2_scan_bwd(x, dt, a, bm, cm, d, states, dy, tile)

    f32 = jnp.float32
    wide, scalar = (b, t, heads * K.HEAD_DIM), (b, t, heads)
    text = jax.jit(both).lower(
        arg(wide), arg(scalar, f32), arg(scalar, f32),
        arg((b, t, groups * K.STATE)), arg((b, t, groups * K.STATE)),
        arg((heads,), f32), arg(wide)).compile().as_text()
    assert "mamba2.chunk.fwd" in text and "mamba2.chunk.bwd" in text
    # the eight head blocks' partial dB and dC, summed by XLA
    assert "f32[8,1,16384,128]" in text


def test_heads_of_64_at_16384_positions_compile_fused(one_chip,
                                                      real_kernels):
    """32 query heads over 8 key/value heads of 64 at 16,384 positions:
    the ONE backward call keeps dq's, dk's and dv's float32 rows
    resident, and a row of 64 features takes a lane tile of 128: the
    count that sets Mosaic's limit has to say so (49 MB allocated where
    the count by features said 32)."""
    b, h, hk, t, dh = 1, 32, 8, 16384, 64
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=h // hk) == "fused"

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                               scale=0.015625)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(hk), arg(hk)).compile().as_text()
    _holds_the_calls(text, "fused")
