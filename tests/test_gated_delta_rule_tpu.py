"""Hardware checks of the gated delta rule's ``gdn.*`` Pallas kernels on
a real TPU at dk = dv = 128: against the float32 recurrence at 1024
positions and, at qwen3next-train-s8192's own call (8192 positions, 16
key and 32 value heads), against the chunked XLA form on the same
device. Skipped on CPU backends (the interpreter's run at small shapes
is tests/test_gated_delta_rule_kernel.py). Run on the chip in one
pytest process:

    PT_TEST_TPU=1 python -m pytest tests/test_gated_delta_rule_tpu.py -q
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.parallel import gated_delta_rule as gdr

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")

BF, F32 = jnp.bfloat16, jnp.float32
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# max |kernel - reference| over max |reference|: every product of a
# chunk is rounded to bf16 once or twice on its way into the state
# (0.0025-0.0052 seen against the float32 recurrence at 1024 positions,
# 0.0065 for the chunked XLA form: my chip runs, PR 33 and PR 32)
REL_TOL = 0.02


def _operands(t, hk, hv, seed):
    r = np.random.RandomState(seed)
    q, k = (jnp.asarray(r.randn(1, t, hk, 128), BF) for _ in "qk")
    v, do = (jnp.asarray(r.randn(1, t, hv, 128), BF) for _ in "vd")
    return (q, k, v, -jnp.asarray(r.rand(1, t, hv) * 0.5, F32),
            jnp.asarray(r.rand(1, t, hv), F32), do)


def _op(q, k, v, g, beta, do):
    ins = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
    out = L._gated_delta_rule(ins, {"chunk": 64})
    grads = L._gated_delta_rule_grad(
        {**ins, "States": out["States"], "GRAD::Out": [do]}, {"chunk": 64})
    return (out["Out"][0], *(grads[f"GRAD::{s}"][0] for s in (
        "Q", "K", "V", "G", "Beta")), out["States"][0])


def _recurrence(q, k, v, g, beta, do):
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(L.recurrent_gated_delta_rule, q.astype(F32),
                           k.astype(F32), v.astype(F32), g, beta)
        return (out, *vjp(do.astype(F32)))


def _rel(a, b):
    a, b = (jnp.asarray(x, F32) for x in (a, b))
    assert bool(jnp.isfinite(a).all())
    return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(), 1e-6))


@pytest.mark.parametrize("t,hk,hv", [(1024, 2, 4), (1000, 2, 2)],
                         ids=["t1024_rep2", "t1000_padded"])
def test_against_the_float32_recurrence(t, hk, hv):
    args = _operands(t, hk, hv, seed=t)
    assert gdr.gdn_tile(t, hk, hv, 128, 128, 64, BF) == (hv // hk, 8)
    got = jax.jit(_op)(*args)
    for name, a, b in zip(NAMES, got, jax.jit(_recurrence)(*args)):
        assert _rel(a, b) <= REL_TOL, (name, _rel(a, b))


def test_the_cells_call_against_the_chunked_form(monkeypatch):
    """b1 t8192 hk16 hv32: 128 chunks, 16 grid steps a key head. The
    recurrence in float32 over 8192 steps is the cell's reference
    already (perf/reference); here the XLA form the kernels replace."""
    args = _operands(8192, 16, 32, seed=0)
    assert gdr.gdn_tile(8192, 16, 32, 128, 128, 64, BF) == (2, 8)
    got = jax.jit(_op)(*args)
    monkeypatch.setattr(gdr, "kernels_enabled", lambda: False)
    want = jax.jit(_op)(*args)
    for name, a, b in zip(NAMES + ("states",), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) <= REL_TOL, (name, _rel(a, b))


def test_the_lowered_calls_are_the_programs_kernels():
    args = [jax.ShapeDtypeStruct(x.shape, x.dtype)
            for x in _operands(8192, 16, 32, seed=0)]
    text = jax.jit(_op).lower(*args).as_text()
    assert "gdn.rule.fwd" in text and "gdn.rule.bwd" in text
    assert "triangular" not in text and "while" not in text
