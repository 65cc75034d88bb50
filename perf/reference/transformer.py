"""Plain reference of the encoder-decoder translation model (Vaswani et
al. 2017, base; pre-norm blocks and untied embeddings as the repo
builds it): eval-mode forward loss, and the logits a greedy decoder
sees. Independent of the program: weights in, numbers out."""

import jax
import jax.numpy as jnp

from perf.reference.common import attention, encoder_layer, layer_norm


def _embed(ids, table, pos_table, d):
    t = ids.shape[1]
    return table[ids] * jnp.sqrt(jnp.float32(d)) + pos_table[:t][None]


def encode(w, cfg, src_ids, src_pad):
    x = _embed(src_ids, w["src_emb.w"], w["src_pos.w"], cfg["d_model"])
    for i in range(cfg["n_layer"]):
        x = encoder_layer(w, i, x, src_pad, cfg["n_head"])
    return layer_norm(x, w["enc_post_ln.scale"], w["enc_post_ln.bias"])


def decode_logits(w, cfg, enc, src_pad, trg_ids, trg_pad=None):
    """Logits [b, t, vocab] of the decoder over ``trg_ids`` (causal)."""
    nh = cfg["n_head"]
    x = _embed(trg_ids, w["trg_emb.w"], w["trg_pos.w"], cfg["d_model"])

    def fc(h, name):
        return h @ w[f"{name}.w"] + w[f"{name}.b"]

    for i in range(cfg["n_layer"]):
        p = f"dec{i}"
        h = layer_norm(x, w[f"{p}_preself_ln.scale"],
                       w[f"{p}_preself_ln.bias"])
        a = attention(fc(h, f"{p}_self_q_colp"), fc(h, f"{p}_self_k_colp"),
                      fc(h, f"{p}_self_v_colp"), nh, key_pad=trg_pad,
                      causal=True)
        x = x + fc(a, f"{p}_self_out_rowp")
        h = layer_norm(x, w[f"{p}_precross_ln.scale"],
                       w[f"{p}_precross_ln.bias"])
        a = attention(fc(h, f"{p}_cross_q_colp"),
                      fc(enc, f"{p}_cross_k_colp"),
                      fc(enc, f"{p}_cross_v_colp"), nh, key_pad=src_pad)
        x = x + fc(a, f"{p}_cross_out_rowp")
        h = layer_norm(x, w[f"{p}_preffn_ln.scale"],
                       w[f"{p}_preffn_ln.bias"])
        h = jax.nn.relu(fc(h, f"{p}_ffn1_colp"))
        x = x + fc(h, f"{p}_ffn2_rowp")
    x = layer_norm(x, w["dec_post_ln.scale"], w["dec_post_ln.bias"])
    return x @ w["proj_colp.w"]


def loss(w, cfg, feed):
    """Label-smoothed cross entropy, mean over real target tokens."""
    src_pad = jnp.asarray(feed["src_pad_mask"], jnp.float32)
    trg_pad = jnp.asarray(feed["trg_pad_mask"], jnp.float32)
    enc = encode(w, cfg, jnp.asarray(feed["src_ids"]), src_pad)
    logits = decode_logits(w, cfg, enc, src_pad,
                           jnp.asarray(feed["trg_ids"]), trg_pad)
    v = logits.shape[-1]
    eps = cfg["label_smooth_eps"]
    target = jax.nn.one_hot(jnp.asarray(feed["lbl_ids"]), v) * (1 - eps) \
        + eps / v
    ce = -jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)
    return jnp.sum(ce * trg_pad) / jnp.maximum(jnp.sum(trg_pad), 1.0)


def greedy_logits(w, cfg, src, tokens, bos_id=0):
    """For one request: the full-forward logits [len(tokens), vocab]
    that a greedy decoder sees at each step, teacher-forced on the
    tokens the engine emitted (row i is computed from BOS + tokens[:i]).
    No cache: one causal pass over the whole prefix."""
    src_ids = jnp.asarray(src)[None]
    enc = encode(w, cfg, src_ids, None)
    tokens = jnp.asarray(tokens)
    prefix = jnp.concatenate(
        [jnp.full((1,), bos_id, tokens.dtype), tokens[:-1]])[None]
    return decode_logits(w, cfg, enc, None, prefix)[0]
