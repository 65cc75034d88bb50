"""What NVIDIA-Nemotron-3-Nano-30B-A3B's cell brings to the chip compiles
for a TPU v5e on this CPU-only machine, in the way of
tests/test_attention_compiles_for_v5e.py (one more file, so that one
more worker loads libtpu): grouped-query attention at 32 query heads
over 2 key/value heads of 128 (a group of 16, the widest so far) x 4096,
its backward ONE call; the experts' grouped matmuls at a width off the
128 lanes (2688 -> 1856 -> 2688, 8 of 128 held). The Mamba-2 scan's
kernels: tests/test_mamba2_scan_tpu.py. Nothing runs, so this says
nothing about results or times."""

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as fa
from paddle_tpu.parallel import grouped_matmul as gm

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    _holds_the_calls, one_chip, real_kernels)


def test_grouped_query_attention_at_a_group_of_16_compiles(one_chip,
                                                           real_kernels):
    """32 query heads over 2 key/value heads of 128 at 4096 positions:
    one head a step at blocks of 512; the backward call's grid walks a
    group's 16 heads and writes [b, 2, t, 128] from rows resident in
    VMEM: ``bhtd_bwd_form`` answers ``fused``."""
    b, h, hk, t, dh = 1, 32, 2, 4096, 128
    assert fa.bhtd_tile(h, t, t, dh=dh, group=h // hk) == (1, 512, 512)
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=h // hk) == "fused"

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(hk), arg(hk)).compile().as_text()
    _holds_the_calls(text, "fused")
    # K and V are read where they lie: nothing [b, 32, t, 128] of them
    assert "bf16[1,2,4096,128]" in text


@pytest.mark.parametrize("k,n,tile,dx_tile", [
    (2688, 1856, (128, 2688, 640), (128, 1856, 896)),
    (1856, 2688, (128, 1856, 896), (128, 2688, 640)),
], ids=["up_2688_to_1856", "down_1856_to_2688"])
def test_off_lane_grouped_matmuls_compile(k, n, tile, dx_tile, one_chip,
                                          real_kernels):
    """One chip's 8 of 128 relu^2 experts of 1856 = 14.5 x 128 over a
    hidden size of 2688 = 21 x 128: a buffer of 24,576 rows of which an
    even router fills 1536. As a contraction the width off the lanes is
    taken whole (its last 64 lanes masked in VMEM); as a result's width
    it is three blocks of 640 of which the last hangs 64 lanes over the
    array's edge, under the whole contraction of 2688."""
    m, e, live = 24576, 8, 1536
    bf = jnp.bfloat16
    assert gm.gmm_tile(m, k, n, e, bf, "tpu", False, live_rows=live) == tile
    assert gm.gmm_tile(m, n, k, e, bf, "tpu", False,
                       live_rows=live) == dx_tile

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def three(lhs, rhs, g, sizes):
        return (gm.gmm(lhs, rhs, sizes, tile, zero_behind=True),
                gm.gmm(g, rhs, sizes, dx_tile, transpose_rhs=True,
                       name="moe.gmm.bwd_dx"),
                gm.tgmm(lhs, g, sizes, tile))

    text = jax.jit(three).lower(
        arg((m, k), bf), arg((e, k, n), bf), arg((m, n), bf),
        arg((e,), jnp.int32)).compile().as_text()
    for name in ("moe.gmm.fwd", "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"):
        assert name in text, name
