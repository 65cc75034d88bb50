"""Hardware checks of the selective scan's ``ssm.scan.*`` Pallas kernels
on a real TPU: against the chunked XLA form on the same device (float32
state either way) at phi4flash-train-s4096's own calls (4096 positions
of 5120 channels x 16 states, gated and not) and at a row the block does
not divide. Skipped on CPU backends (the interpreter's run at small
shapes is tests/test_selective_scan.py). Run on the chip in one pytest
process:

    PT_TEST_TPU=1 python -m pytest tests/test_selective_scan_tpu.py -q
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import selective_scan_ops as S
from paddle_tpu.parallel import selective_scan as K

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")

BF, F32 = jnp.bfloat16, jnp.float32
# max |kernel - XLA form| over max |XLA form|: both keep the state in
# float32 and round x, dt, z and the results to bf16; what differs is the
# order of float32 sums (0.004-0.008 seen, the rounding of a bf16 result:
# my chip runs, PR 40)
REL_TOL = 0.02


def _operands(t, e, gated, seed):
    r = np.random.RandomState(seed)
    n = K.STATE
    ins = {"X": jnp.asarray(r.randn(1, t, e), BF),
           "Dt": jnp.asarray(r.randn(1, t, e) - 3.0, BF),
           "A": -jnp.asarray(np.tile(np.arange(1, n + 1), (e, 1))
                             * np.exp(r.randn(e, 1) * 0.2), F32),
           "B": jnp.asarray(r.randn(1, t, n), BF),
           "C": jnp.asarray(r.randn(1, t, n), BF),
           "D": jnp.asarray(r.randn(e), F32),
           "DtBias": jnp.asarray(r.randn(e) * 0.5, F32)}
    if gated:
        ins["Z"] = jnp.asarray(r.randn(1, t, e), BF)
    return ins, jnp.asarray(r.randn(1, t, e), BF)


def _op(ins, dy, kernels):
    """(out, grads by slot) as the op lowers the call; ``kernels`` False
    holds it to the chunked XLA form."""
    tile = K.ssm_tile
    if not kernels:
        K.ssm_tile = lambda *a, **k: None
    try:
        def run(ins, dy):
            wrapped = {k: [v] for k, v in ins.items()}
            out = S._selective_scan(wrapped, {})
            grads = S._selective_scan_grad(
                {**wrapped, "States": out["States"], "GRAD::Out": [dy]}, {})
            return out["Out"][0], {k: v[0] for k, v in grads.items()}

        fn = jax.jit(run)
        out = jax.block_until_ready(fn(ins, dy))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(ins, dy))
        return out, time.perf_counter() - t0
    finally:
        K.ssm_tile = tile


def _rel(a, b):
    a, b = (jnp.asarray(x, F32) for x in (a, b))
    assert bool(jnp.isfinite(a).all())
    return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(), 1e-6))


@pytest.mark.parametrize("t,e,gated", [
    (4096, 5120, True), (4096, 5120, False), (1000, 1024, True)])
def test_kernels_match_the_xla_form(t, e, gated):
    assert K.ssm_tile(t, e, K.STATE, BF) is not None
    ins, dy = _operands(t, e, gated, seed=t + gated)
    (y, grads), kernel_s = _op(ins, dy, kernels=True)
    (y_ref, grads_ref), xla_s = _op(ins, dy, kernels=False)
    errs = {"Out": _rel(y, y_ref)}
    errs.update({k: _rel(grads[k], grads_ref[k]) for k in grads_ref})
    print(f"\nssm.scan t{t} e{e} gated={gated}: kernels "
          f"{kernel_s * 1e3:.2f} ms, XLA form {xla_s * 1e3:.2f} ms "
          f"(forward + backward), rel err {errs}")
    assert set(grads) == set(grads_ref)
    assert max(errs.values()) < REL_TOL, errs
