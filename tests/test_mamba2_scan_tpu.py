"""The Mamba-2 scan's ``mamba2.chunk.*`` Pallas kernels and the chip.

1. They COMPILE for a TPU v5e at nemotron3nano-train-s4096's own call
   (64 heads of 64 in 8 groups over a state of 128 x 4096 positions) and
   at a row of fewer chunks than a grid step holds, on this CPU-only
   machine (tier-1; the way of tests/test_attention_compiles_for_v5e.py:
   the TPU's compiler is installed and compiles for a chip that is
   described, not attached; nothing runs).
2. Hardware checks on a real TPU, against the chunked XLA form on the
   same device at the cell's call and at a row the chunk does not
   divide. Skipped on CPU backends (the interpreter's run at small
   shapes is tests/test_mamba2_scan.py). Run on the chip in one pytest
   process:

    PT_TEST_TPU=1 python -m pytest tests/test_mamba2_scan_tpu.py -q -s
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import mamba2_scan_ops as S
from paddle_tpu.parallel import mamba2_scan as K

from test_attention_compiles_for_v5e import (  # noqa: F401  (fixtures)
    one_chip, real_kernels)

BF, F32 = jnp.bfloat16, jnp.float32
HEADS, GROUPS = 64, 8
on_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")
# max |kernel - XLA form| over max |XLA form|: both keep the state in
# float32 and round x, B, C and the results to bf16; the XLA form
# multiplies in float32 where the kernels' operands are bf16
REL_TOL = 0.02


@pytest.mark.parametrize("t,heads,groups", [(4096, HEADS, GROUPS),
                                            (300, 2, 1)],
                         ids=["nemotron3nano_s4096", "t300_one_step"])
def test_kernels_compile_for_v5e(t, heads, groups, one_chip, real_kernels):
    tile = K.mamba2_tile(t, heads, groups, K.HEAD_DIM, K.STATE, K.CHUNK, BF,
                         "tpu", False)
    assert tile == (heads // groups // 2, min(8, -(-t // K.CHUNK)))

    def arg(shape, dt=BF):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def both(x, dt, a, b, c, d, dy):
        y, states = K.mamba2_scan_fwd(x, dt, a, b, c, d, tile)
        return y, K.mamba2_scan_bwd(x, dt, a, b, c, d, states, dy, tile)

    x, s = arg((1, t, heads * K.HEAD_DIM)), arg((1, t, heads), F32)
    bc = arg((1, t, groups * K.STATE))
    text = jax.jit(both).lower(x, s, s, bc, bc, arg((heads,), F32),
                               x).compile().as_text()
    for name in ("mamba2.chunk.fwd", "mamba2.chunk.bwd"):
        assert name in text, name
    # nothing of size t x heads x 64 x 128: the states are a chunk's
    n = -(-t // K.CHUNK)
    assert f"f32[{n},1,{heads // 2},128,128]" in text or n % tile[1]
    assert f"f32[1,{t},{heads}," not in text


def _operands(t, heads, groups, seed):
    r = np.random.RandomState(seed)
    ins = {"X": jnp.asarray(r.randn(1, t, heads * K.HEAD_DIM), BF),
           "Dt": jnp.asarray(r.randn(1, t, heads) - 3.0, BF),
           "ALog": jnp.asarray(np.log(np.arange(1, heads + 1)), F32),
           "B": jnp.asarray(r.randn(1, t, groups * K.STATE) * 0.5, BF),
           "C": jnp.asarray(r.randn(1, t, groups * K.STATE) * 0.5, BF),
           "D": jnp.ones((heads,), F32),
           "DtBias": jnp.asarray(r.randn(heads) * 0.5, F32)}
    return ins, jnp.asarray(r.randn(1, t, heads * K.HEAD_DIM), BF)


def _scan(ins, dy, groups):
    attrs = {"groups": groups, "chunk": K.CHUNK}
    wrapped = {k: [v] for k, v in ins.items()}
    out = S._mamba2_scan(wrapped, attrs)
    grads = S._mamba2_scan_grad(
        {**wrapped, "States": out["States"], "GRAD::Out": [dy]}, attrs)
    return {"Out": out["Out"][0], **{k: v[0] for k, v in grads.items()}}


def _timed(fn, *args, calls=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


@on_tpu
@pytest.mark.parametrize("t,heads,groups", [(4096, HEADS, GROUPS),
                                            (1100, 16, 2)],
                         ids=["nemotron3nano_s4096", "t1100_uneven"])
def test_kernels_match_the_chunked_xla_form(t, heads, groups, monkeypatch):
    ins, dy = _operands(t, heads, groups, seed=t)
    assert K.mamba2_tile(t, heads, groups, K.HEAD_DIM, K.STATE, K.CHUNK,
                         BF) is not None
    kernels = jax.jit(lambda ins, dy: _scan(ins, dy, groups))
    got = jax.block_until_ready(kernels(ins, dy))
    ms_kernel = _timed(kernels, ins, dy)
    monkeypatch.setattr(K, "mamba2_tile", lambda *a, **k: None)
    xla = jax.jit(lambda ins, dy: _scan(ins, dy, groups))
    want = jax.block_until_ready(xla(ins, dy))
    ms_xla = _timed(xla, ins, dy)
    print(f"\nmamba2 scan fwd+bwd at t{t} h{heads} g{groups}: kernels "
          f"{ms_kernel:.2f} ms, chunked XLA form {ms_xla:.2f} ms")
    assert set(got) == set(want)
    for k in want:
        a, b = (np.asarray(v, np.float32) for v in (got[k], want[k]))
        assert np.isfinite(a).all(), k
        assert np.abs(a - b).max() <= REL_TOL * max(np.abs(b).max(), 1e-6), k
