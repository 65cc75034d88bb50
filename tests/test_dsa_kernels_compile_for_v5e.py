"""What Keye-VL-2.0's cell brings to the chip compiles for a TPU v5e on
this CPU-only machine, in the way of
tests/test_attention_compiles_for_v5e.py (one more file, so that one
more worker loads libtpu): ``dsa.score.fwd`` (parallel/dsa_score.py) at
keye-train-s16384's call (16 index heads of 64, a chunk of 512 queries
against 16,384 keys in blocks of 512) and ``dsa.topk.fwd`` (a chunk's
thresholds over its causal prefix, 32 MB of keys in VMEM), alone and
inside the op ``dsa_select``; and ``attn.bhtd.fwd`` and the ONE
``attn.bhtd.bwd`` under a SELECTION with its live table, at the cell's
32 / 4 heads of 128 over 16,384 positions. Nothing runs, so this says
nothing about results or times: tests/test_dsa_ops.py holds the kernels
to XLA's form through the interpreter."""

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import dsa_ops
from paddle_tpu.parallel import dsa_score
from paddle_tpu.parallel import flash_attention as fa

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    _calls, one_chip, real_kernels)


@pytest.fixture
def on_a_tpu(monkeypatch, real_kernels):
    monkeypatch.setattr(dsa_score, "kernels_enabled", lambda: True)
    monkeypatch.setattr(dsa_score, "_INTERPRET", False)


def arg(shape, dt, chip):
    return jax.ShapeDtypeStruct(shape, dt, sharding=chip)


def test_the_score_kernel_compiles_at_the_cells_chunk(one_chip, on_a_tpu):
    assert dsa_score.score_tile(512, 512, 16, 64, on_mesh=False)
    assert not dsa_score.score_tile(512, 512, 16, 64, on_mesh=True)
    assert not dsa_score.score_tile(512, 64, 16, 64, on_mesh=False)
    text = jax.jit(lambda c, qi, ki, w: dsa_score.score_rows(
        c, qi, ki, w, 1 / 32, 512)).lower(
            arg((), jnp.int32, one_chip),
            arg((16, 512, 64), jnp.bfloat16, one_chip),
            arg((16384, 64), jnp.bfloat16, one_chip),
            arg((512, 16), jnp.float32, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa.score.fwd" in text


def test_the_threshold_kernel_compiles_at_the_cells_chunk(one_chip,
                                                          on_a_tpu):
    assert dsa_score.topk_tile(512, 512, 16384, on_mesh=False)
    assert not dsa_score.topk_tile(512, 512, 16384, on_mesh=True)
    assert not dsa_score.topk_tile(512, 512, 32768, on_mesh=False)
    text = jax.jit(lambda c, scores: dsa_score.threshold_rows(
        c, scores, 2048, 512)).lower(
            arg((), jnp.int32, one_chip),
            arg((512, 16384), jnp.float32, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa.topk.fwd" in text


def test_the_select_op_compiles_around_the_kernel(one_chip, on_a_tpu):
    """``dsa_select`` at the cell's row: the four chunks that need no
    top-k under one ``lax.map`` (their scores), the others under a
    second (scores, thresholds, the position bisection behind a
    ``lax.cond``); its largest temporaries are a chunk's [512, 16384]
    rows, never [16384, 16384] float32."""
    attrs = {"scale": dsa_ops.index_scale(16, 64), "topk": 2048,
             "q_chunk": 512, "kv_chunk": 512}
    compiled = jax.jit(lambda qi, ki, w: dsa_ops._dsa_select(
        {"QI": [qi], "KI": [ki], "W": [w]}, attrs)).lower(
            arg((1, 16, 16384, 64), jnp.bfloat16, one_chip),
            arg((1, 1, 16384, 64), jnp.bfloat16, one_chip),
            arg((1, 16384, 16), jnp.bfloat16, one_chip)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert text.count("dsa.score.fwd") >= 2 and "dsa.topk.fwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 600 * 2**20


def test_attention_under_a_selection_compiles_at_the_cells_call(
        one_chip, real_kernels):
    h, hk, t, dh = 32, 4, 16384, 128
    assert fa.bhtd_selected(h, t, t, dh=dh, group=h // hk, blocks=(32, 32))
    q = arg((1, h, t, dh), jnp.bfloat16, one_chip)
    kv = arg((1, hk, t, dh), jnp.bfloat16, one_chip)
    sel = arg((1, t // 32, t), jnp.int32, one_chip)
    live = arg((1, t // 512, t // 512), jnp.int32, one_chip)

    def both(q_, k_, v_, g_, sel_, live_):
        out, lse = fa.flash_attention_fwd(q_, k_, v_, causal=True,
                                          selected=sel_, live=live_)
        return fa.flash_attention_bwd(q_, k_, v_, None, None, out, lse, g_,
                                      causal=True, selected=sel_, live=live_)

    text = jax.jit(both).lower(q, kv, kv, q, sel, live).compile().as_text()
    assert _calls(text) == {"attn.bhtd.fwd", "attn.bhtd.bwd"}
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_loss_kernel_compiles_at_the_cells_row(one_chip, on_a_tpu):
    """``dsa.loss.bwd`` at keye-train-s16384's row: 16 index heads of 64,
    the attention's 32 / 4 heads of 128, tiles of 512 x 512, the packed
    selection; and the op ``dsa_index_loss`` around it holds no
    [16384, 16384] value."""
    assert dsa_score.loss_tile(512, 512, 16, 64, on_mesh=False)
    assert not dsa_score.loss_tile(512, 512, 16, 64, on_mesh=True)
    assert not dsa_score.loss_tile(128, 512, 16, 64, on_mesh=False)
    t = 16384
    attrs = {"scale": dsa_ops.index_scale(16, 64), "attn_scale": 128 ** -0.5,
             "q_chunk": 512, "kv_chunk": 512}
    slots = {"QI": ((1, 16, t, 64), jnp.bfloat16),
             "KI": ((1, 1, t, 64), jnp.bfloat16),
             "W": ((1, t, 16), jnp.bfloat16),
             "Q": ((1, 32, t, 128), jnp.bfloat16),
             "K": ((1, 4, t, 128), jnp.bfloat16),
             "Lse": ((1, 32, t, 1), jnp.float32),
             "Selected": ((1, t // 32, t), jnp.int32),
             "IndexLse": ((1, t), jnp.float32)}
    compiled = jax.jit(lambda *a: dsa_ops._dsa_index_loss(
        {s: [x] for s, x in zip(slots, a)}, attrs)).lower(
            *(arg(shape, dt, one_chip) for shape, dt in slots.values())
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa.loss.bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2**20
