"""What Xing4.0-29B-A4B's cell brings to the chip compiles for a TPU v5e
on this CPU-only machine, in the way of
tests/test_attention_compiles_for_v5e.py (one more file, so that one
more worker loads libtpu): the Sinkhorn iterations of a hyper-connection's
mix, ``hc.mix.fwd`` / ``hc.mix.bwd`` (parallel/hc_mix.py), at
xing4-train-s4096's call (a 4 x 4 matrix a token, 4096 tokens, 20
iterations, 8 rows of 128 tokens a grid step), alone and inside the ops
``hc_mix`` / ``hc_mix_grad`` at the cell's streams. Nothing runs, so this
says nothing about results or times: tests/test_hc_ops.py holds the
kernels to XLA's form and to autodiff through the interpreter."""

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import hc_ops
from paddle_tpu.parallel import hc_mix

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    one_chip, real_kernels)

ATTRS = {"n": 4, "epsilon": 1e-6, "iters": 20, "hc_eps": 1e-6,
         "clamp_min": -30.0, "clamp_max": 30.0}


@pytest.fixture
def on_a_tpu(monkeypatch, real_kernels):
    monkeypatch.setattr(hc_mix, "kernels_enabled", lambda: True)
    monkeypatch.setattr(hc_mix, "_INTERPRET", False)


@pytest.mark.parametrize("tokens", [4096, 1024])
def test_sinkhorn_kernels_compile(tokens, one_chip, on_a_tpu):
    """Forward, and the backward pass with its forty kept half-steps in
    VMEM scratch: straight-line code on [8, 128] tiles picked by their
    leading index passes Mosaic, one call a pass."""
    assert hc_mix.mix_tile(4, tokens) == 8
    z = jax.ShapeDtypeStruct((16, tokens), jnp.float32, sharding=one_chip)

    def both(z_, d_):
        return (hc_mix.sinkhorn_fwd(z_, 4, 20, 1e-6, -30.0, 30.0, 8),
                hc_mix.sinkhorn_bwd(z_, d_, 4, 20, 1e-6, -30.0, 30.0, 8))

    text = jax.jit(both).lower(z, z).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("hc.mix.fwd", "hc.mix.bwd"):
        assert name in text, name


def test_the_mix_ops_compile_around_the_kernels_at_the_cells_streams(
        one_chip, on_a_tpu):
    """``hc_mix`` and ``hc_mix_grad`` on four bf16 streams of 3584 over
    4096 tokens: the projection, the kernels and the products around
    them in one jit, no float32 copy of the streams between them."""
    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arg((1, 4096, 4 * 3584), jnp.bfloat16)
    phi, bias, alpha = arg((4 * 3584, 24)), arg((24,)), arg((3,))
    pre, res = arg((1, 4, 4096)), arg((1, 4, 4, 4096))

    def both(x_, phi_, bias_, alpha_, d_pre, d_post, d_res):
        ins = {"X": [x_], "Phi": [phi_], "Bias": [bias_], "Alpha": [alpha_]}
        return (hc_ops._hc_mix(ins, ATTRS), hc_ops._hc_mix_grad(
            {**ins, "GRAD::HPre": [d_pre], "GRAD::HPost": [d_post],
             "GRAD::HRes": [d_res]}, ATTRS))

    compiled = jax.jit(both).lower(x, phi, bias, alpha, pre, pre,
                                   res).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # the largest temporaries are stream-sized in bf16 (117 MB), not
    # their float32 copies
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 4096 * 14336 * 2
