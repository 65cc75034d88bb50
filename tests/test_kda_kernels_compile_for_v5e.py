"""What Kimi-Linear-48B-A3B's cell brings to the chip compiles for a TPU
v5e on this CPU-only machine, in the way of
tests/test_attention_compiles_for_v5e.py (one more file, so that one
more worker loads libtpu): the delta rule with a decay a key feature,
``kda.rule.fwd`` / ``kda.rule.bwd``, at kimilinear-train-s4096's call
(1 x 4096 positions, 32 heads of 128 with keys of their own, two heads
and 8 chunks of 64 a grid step), inside the VMEM count ``kda_tile``
holds the call to. Nothing runs, so this says nothing about results or
times: tests/test_kda_rule.py holds the kernels to the recurrence
through the interpreter."""

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import flags, monitor
from paddle_tpu.core import interp
from paddle_tpu.ops import linear_attention_ops as lin
from paddle_tpu.parallel import gated_delta_rule as gdr

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    one_chip, real_kernels)

BF = jnp.bfloat16
# the cell's call, and a sequence of fewer chunks than a grid step holds
# with an odd number of heads (one head a step, beside an empty half)
CALLS = {"kimilinear_s4096": (4096, 32), "t200_three_heads": (200, 3)}


def _args(t, h, one_chip):
    def arg(shape, dt=BF):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arg((1, t, h, 128))
    return (x, x, x, arg((1, t, h, 128), jnp.float32),
            arg((1, t, h), jnp.float32))


@pytest.mark.parametrize("call", sorted(CALLS))
def test_kda_kernels_compile(call, one_chip, real_kernels):
    """Forward and the backward pass from the saved states: the running
    sums' and the levels' sublane rolls, the transposed row factors of
    the state, the levels' batched products and the blocks' VMEM pass
    Mosaic, one call a pass."""
    t, h = CALLS[call]
    tile = gdr.kda_tile(t, h, h, 128, 128, 64, BF, "tpu", False)
    assert tile == (2 - h % 2, min(8, -(-t // 64)))
    assert (gdr._vmem_bytes(*tile, 128, 128, True)
            <= gdr._VMEM_CAP_BYTES)

    def both(q, k, v, g, beta, do):
        o, states = gdr.gated_delta_rule_fwd(q, k, v, g, beta, tile)
        return o, gdr.gated_delta_rule_bwd(q, k, v, g, beta, states, do,
                                           tile)

    q, k, v, g, beta = _args(t, h, one_chip)
    text = jax.jit(both).lower(q, k, v, g, beta, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("kda.rule.fwd", "kda.rule.bwd"):
        assert name in text, name
    assert "gdn.rule" not in text
    # no copy in front: g is read where it lies, nothing float32 of its
    # size is staged heads first; dG leaves as [b, t, h * dk]
    assert f"f32[1,{h},{t},128]" not in text


def test_the_op_reports_kernel_and_feature(one_chip, real_kernels,
                                           monkeypatch):
    """The op's own lowering of the cell's call, forward and grad op:
    ``pt_linear_attention_dispatch_total`` counts one row a pass with
    ``impl=kernel gate=feature``; a decay a head keeps ``gate=head``."""
    monkeypatch.setattr(gdr, "kernels_enabled", lambda: True)
    monkeypatch.setattr(interp, "lowering_active", lambda: True)
    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        q, k, v, g, beta = _args(4096, 32, one_chip)
        ins = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}

        def fwd(q, k, v, g, beta):
            return lin._gated_delta_rule(
                {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
                {})["Out"][0]

        jax.jit(fwd).lower(*(x[0] for x in ins.values()))
        head = jax.ShapeDtypeStruct((1, 4096, 32), jnp.float32,
                                    sharding=one_chip)
        jax.jit(fwd).lower(q, k, v, head, beta)
        rows = {(r["labels"]["impl"], r["labels"]["gate"]): r["value"]
                for r in monitor.snapshot()[lin._M_DISPATCH.name]["values"]}
        assert rows == {("kernel", "feature"): 1, ("kernel", "head"): 1}
        assert lin.dispatch_counts() == {
            "kernel fwd b1 t4096 hk32 hv32 dk128 dv128 chunk64": 2}
    finally:
        monitor.reset()
        flags.set_flags({"telemetry": False})
