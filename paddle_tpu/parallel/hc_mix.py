"""``hc.mix.fwd`` / ``hc.mix.bwd``: the Sinkhorn iterations of a
hyper-connection's mix (ops/hc_ops.py) as ONE Pallas kernel a pass.

    M0 = exp(clamp(Z, lo, hi))                       Z [n n, T], a column a token
    iters x: M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)

As XLA's ops the forty half-steps are a chain of some eighty small
fusions forward and twice that backward (XLA makes no single loop of
them), each a few hundred KB and a launch: latency, ten sublayers a
step. Here a grid step holds ``rows`` x 128 tokens: Z comes as
[n n, T / 128, 128], so that entry (j, i) of every token of the block is
one [rows, 128] tile (whole vregs at rows = 8) picked by its leading
index, a row or column sum is n - 1 adds of such tiles, and the
iterations are straight-line code on registers.

The backward pass makes the iterations again (hc_ops: nothing of the mix
lives from the forward pass), keeping every half-step's M' and 1 / (s +
eps) in VMEM scratch, then walks back: dM = (dM' - sum(dM' M')) / (s +
eps), the sum over the half-step's axis; at the start dZ = dM0 M0 inside
the clamp and 0 outside. Everything is float32.

``mix_tile`` says whether a call takes the kernels (a TPU or the test
hook, no mesh, whole blocks of tokens) and at how many rows a grid
step."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_LANES = 128
_ROWS = 8            # sublane rows of 128 tokens a grid step: one vreg an entry
# Test hook, as grouped_matmul._INTERPRET: run the kernels in interpreter
# mode on a CPU.
_INTERPRET = False


def kernels_enabled() -> bool:
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def mix_tile(n: int, tokens: int) -> Optional[int]:
    """Rows of 128 tokens a grid step for a mix of n x n over ``tokens``
    tokens, or None where the call takes XLA's ops: no TPU (and no test
    hook), a mesh, or tokens that are not whole blocks."""
    if not kernels_enabled() or _under_mesh():
        return None
    if n < 1 or tokens % (_LANES * _ROWS):
        return None
    return _ROWS


def _start(z_ref, n, lo, hi):
    """M0 as n rows of n tiles, and Z's tiles."""
    z = [[z_ref[j * n + i] for i in range(n)] for j in range(n)]
    return [[jnp.exp(jnp.clip(z[j][i], lo, hi)) for i in range(n)]
            for j in range(n)], z


def _half_step(m, n, axis, eps):
    """One normalisation of m (n rows of n tiles) over ``axis`` (1: a
    row's sum over i; 0: a column's over j) -> (m', [1 / (s + eps)])."""
    invs = []
    for a in range(n):
        line = [m[a][i] if axis == 1 else m[i][a] for i in range(n)]
        s = line[0]
        for x in line[1:]:
            s = s + x
        invs.append(1.0 / (s + eps))
    return [[m[j][i] * invs[j if axis == 1 else i] for i in range(n)]
            for j in range(n)], invs


def _fwd_kernel(z_ref, o_ref, *, n, iters, eps, lo, hi):
    m, _ = _start(z_ref, n, lo, hi)
    for _ in range(iters):
        for axis in (1, 0):
            m, _ = _half_step(m, n, axis, eps)
    for j in range(n):
        for i in range(n):
            o_ref[j * n + i] = m[j][i]


def _bwd_kernel(z_ref, dh_ref, dz_ref, m_scr, inv_scr, *, n, iters, eps, lo,
                hi):
    m0, z = _start(z_ref, n, lo, hi)
    m, k = m0, 0
    for _ in range(iters):
        for axis in (1, 0):
            m, invs = _half_step(m, n, axis, eps)
            for j in range(n):
                inv_scr[k, j] = invs[j]
                for i in range(n):
                    m_scr[k, j * n + i] = m[j][i]
            k += 1
    dm = [[dh_ref[j * n + i] for i in range(n)] for j in range(n)]
    for k in reversed(range(2 * iters)):
        axis = 1 if k % 2 == 0 else 0
        out = [[m_scr[k, j * n + i] for i in range(n)] for j in range(n)]
        for a in range(n):
            cells = [(a, i) if axis == 1 else (i, a) for i in range(n)]
            t = dm[cells[0][0]][cells[0][1]] * out[cells[0][0]][cells[0][1]]
            for j, i in cells[1:]:
                t = t + dm[j][i] * out[j][i]
            inv = inv_scr[k, a]
            for j, i in cells:
                dm[j][i] = (dm[j][i] - t) * inv
    for j in range(n):
        for i in range(n):
            inside = (z[j][i] > lo) & (z[j][i] < hi)
            dz_ref[j * n + i] = jnp.where(inside, dm[j][i] * m0[j][i], 0.0)


def _blocks(z, rows):
    """Z [n n, T] -> [n n, T / 128, 128], its block and the grid."""
    k, t = z.shape
    z3 = z.astype(_F32).reshape(k, t // _LANES, _LANES)
    spec = pl.BlockSpec((k, rows, _LANES), lambda g: (0, g, 0))
    return z3, spec, (t // _LANES // rows,)


def sinkhorn_fwd(z, n, iters, eps, lo, hi, rows):
    """H_res [n n, T] of the mix's logits Z [n n, T]."""
    z3, spec, grid = _blocks(z, rows)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, iters=iters, eps=eps, lo=lo,
                          hi=hi),
        name="hc.mix.fwd",
        out_shape=jax.ShapeDtypeStruct(z3.shape, _F32),
        grid=grid, in_specs=[spec], out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=bool(_INTERPRET))(z3)
    return out.reshape(z.shape)


def sinkhorn_bwd(z, d_res, n, iters, eps, lo, hi, rows):
    """dZ [n n, T] from Z and dH_res, the iterations made again."""
    z3, spec, grid = _blocks(z, rows)
    d3 = d_res.astype(_F32).reshape(z3.shape)
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, iters=iters, eps=eps, lo=lo,
                          hi=hi),
        name="hc.mix.bwd",
        out_shape=jax.ShapeDtypeStruct(z3.shape, _F32),
        grid=grid, in_specs=[spec, spec], out_specs=spec,
        scratch_shapes=[
            pltpu.VMEM((2 * iters, n * n, rows, _LANES), _F32),
            pltpu.VMEM((2 * iters, n, rows, _LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=bool(_INTERPRET))(z3, d3)
    return out.reshape(z.shape)
