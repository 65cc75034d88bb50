"""Plain reference of BERT pretraining (Devlin et al. 2018, base; the
repo's pre-norm encoder layer, untied MLM projection): eval-mode
masked-LM + next-sentence loss. Weights in, a number out."""

import jax
import jax.numpy as jnp

from perf.reference.common import encoder_layer, layer_norm


def loss(w, cfg, feed):
    ids = jnp.asarray(feed["input_ids"])
    pad = jnp.asarray(feed["pad_mask"], jnp.float32)
    t = ids.shape[1]
    x = (w["bert_tok_emb.w"][ids]
         + w["bert_seg_emb.w"][jnp.asarray(feed["token_type_ids"])]
         + w["bert_pos_emb.w"][:t][None])
    x = layer_norm(x, w["bert_emb_ln.scale"], w["bert_emb_ln.bias"])
    for i in range(cfg["n_layer"]):
        x = encoder_layer(w, i, x, pad, cfg["n_head"])
    x = layer_norm(x, w["enc_post_ln.scale"], w["enc_post_ln.bias"])

    h = jax.nn.gelu(x @ w["mlm_tr_colp.w"] + w["mlm_tr_colp.b"],
                    approximate=False)
    h = layer_norm(h, w["mlm_ln.scale"], w["mlm_ln.bias"])
    logp = jax.nn.log_softmax(h @ w["mlm_proj_colp.w"], -1)
    lbl = jnp.asarray(feed["mlm_labels"])
    masked = (lbl >= 0).astype(jnp.float32)
    ce = -jnp.take_along_axis(logp, jnp.maximum(lbl, 0)[..., None], -1)[..., 0]
    mlm = jnp.sum(ce * masked) / jnp.maximum(jnp.sum(masked), 1.0)

    nsp_logp = jax.nn.log_softmax(x[:, 0] @ w["nsp.w"] + w["nsp.b"], -1)
    nsp = -jnp.mean(jnp.take_along_axis(
        nsp_logp, jnp.asarray(feed["nsp_labels"]), -1))
    return mlm + nsp
