"""The embedding gradient's ``embed.grad`` kernel on the chip, at the two
calls it was written for (phi4flash-train-s4096's 4096 rows into
[25008, 2560], smallthinker-train-s16384's 16,384 into [18992, 2560]):
against XLA's scatter-add on the same device, a float32 and a bf16
cotangent, with ms a call each way. Skipped on CPU backends (the
interpreter's run at small shapes is tests/test_embed_grad.py, the
compile for a described v5e tests/test_attention_compiles_for_v5e.py).
Run on the chip in one pytest process:

    PT_TEST_TPU=1 python -m pytest tests/test_embed_grad_tpu.py -q -s
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import embed_grad as eg

on_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")
CALLS = {"phi4flash_s4096": (4096, 25008, 2560),
         "smallthinker_s16384": (16384, 18992, 2560)}


def _timed(fn, *args, calls=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(calls)]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / calls * 1e3


@on_tpu
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("call", sorted(CALLS))
def test_kernel_matches_the_scatter_add(call, dtype):
    n, vocab, d = CALLS[call]
    r = np.random.RandomState(n)
    # ids as the cells draw them, a padding row's -1 and negative ids
    ids = r.randint(0, vocab, (n,)).astype(np.int32)
    ids[::97] -= vocab
    keys = jnp.asarray(np.where(ids < 0, ids + vocab, ids))
    keys = keys.at[::211].set(-1)
    g = jnp.asarray(r.randn(n, d), dtype)
    tile = eg.embed_grad_tile(n, vocab, d, dtype)
    assert tile is not None

    def xla(g, keys):
        zeros = jnp.zeros((vocab, d), jnp.float32)
        rows = jnp.where((keys >= 0)[:, None], g.astype(jnp.float32), 0.0)
        take = lambda w: jnp.take(w, keys, axis=0)   # noqa: E731
        return jax.vjp(take, zeros)[1](rows)[0]

    kernel = jax.jit(lambda g, keys: eg.embed_grad(g, keys, vocab, tile))
    xla = jax.jit(xla)
    got, want = np.asarray(kernel(g, keys)), np.asarray(xla(g, keys))
    print(f"\nembed grad {call} {jnp.dtype(dtype).name}: kernel "
          f"{_timed(kernel, g, keys):.3f} ms, XLA's scatter-add "
          f"{_timed(xla, g, keys):.3f} ms")
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
