"""Shared pieces of the plain references: straightforward jax.numpy in
float32, no kernels, no cache, no batching tricks. Callers run them
under jax.default_matmul_precision("highest"): on a TPU a float32
matmul otherwise runs as one bf16 pass."""

import jax
import jax.numpy as jnp

NEG = -1e9


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def attention(q, k, v, n_head, key_pad=None, causal=False):
    """q [b, tq, d], k/v [b, tk, d]; key_pad [b, tk] with 1 = real."""
    b, tq, d = q.shape
    tk = k.shape[1]
    dh = d // n_head
    q = q.reshape(b, tq, n_head, dh)
    k = k.reshape(b, tk, n_head, dh)
    v = v.reshape(b, tk, n_head, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    if key_pad is not None:
        s = s + ((1.0 - key_pad) * NEG)[:, None, None, :]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((tq, tk), bool)), s, NEG * 10)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, tq, d)


def encoder_layer(w, i, x, pad, n_head):
    """Pre-norm block: x + attn(ln(x)); x + ffn(ln(x)). Fused q/k/v."""
    p = f"enc{i}"
    h = layer_norm(x, w[f"{p}_preattn_ln.scale"], w[f"{p}_preattn_ln.bias"])
    qkv = h @ w[f"{p}_attn_qkv_colp.w"] + w[f"{p}_attn_qkv_colp.b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    a = attention(q, k, v, n_head, key_pad=pad)
    x = x + a @ w[f"{p}_attn_out_rowp.w"] + w[f"{p}_attn_out_rowp.b"]
    h = layer_norm(x, w[f"{p}_preffn_ln.scale"], w[f"{p}_preffn_ln.bias"])
    h = jax.nn.relu(h @ w[f"{p}_ffn1_colp.w"] + w[f"{p}_ffn1_colp.b"])
    return x + h @ w[f"{p}_ffn2_rowp.w"] + w[f"{p}_ffn2_rowp.b"]


def weights_from_scope(scope):
    """{name: float32 array} of every float variable in a scope."""
    out = {}
    for n in scope.var_names():
        v = scope.find_var(n)
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            out[n] = jnp.asarray(v, jnp.float32)
    return out
