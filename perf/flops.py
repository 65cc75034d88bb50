"""Operations and bytes the algorithms need, computed from shapes.

The train-step arithmetic is copied from bench.analytic_flops_per_step
and bench_family.bert_train_flops_per_step (sound; PERF.md lists the
originals for deletion). A multiply-add counts 2. Padded positions
count: the device computes them. Recomputation does not count."""

from __future__ import annotations

from typing import Dict


def transformer_train_flops(cfg: Dict, batch: int, s: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one encoder-decoder train
    step: backward = 2 x forward; embedding lookups excluded."""
    d, di, n = cfg["d_model"], cfg["d_inner"], cfg["n_layer"]

    def layer(tok, t_kv):
        proj = 4 * 2 * tok * d * d          # q, k, v, out
        ffn = 2 * 2 * tok * d * di
        attn = 2 * 2 * tok * t_kv * d       # q.k^T and p.v over all heads
        return proj + ffn + attn

    enc = n * layer(batch * s, s)
    dec_self = n * layer(batch * t, t)
    # cross attention: q and out on t rows, k and v on s rows. (The
    # original counts only two of these four projections: it is 2.4%
    # low at transformer-base, b x 256 x 256. Corrected here.)
    dec_cross = n * (2 * 2 * batch * t * d * d + 2 * 2 * batch * s * d * d
                     + 2 * 2 * batch * t * s * d)
    logits = 2 * batch * t * d * cfg["trg_vocab_size"]
    return 3.0 * (enc + dec_self + dec_cross + logits)


def bert_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one BERT pretraining step:
    encoder, MLM transform and vocabulary projection over every
    position; the NSP head is negligible and left out."""
    d, di, n = cfg["d_model"], cfg["d_inner"], cfg["n_layer"]
    tok = batch * t
    per_layer = 4 * 2 * tok * d * d + 2 * 2 * tok * d * di \
        + 2 * 2 * tok * t * d
    head = 2 * tok * d * d + 2 * tok * d * cfg["vocab_size"]
    return 3.0 * (n * per_layer + head)


def attention_train_cost(calls: Dict[str, int], cfg: Dict, batch: int,
                         seq: int, bytes_per_el: int = 2
                         ) -> Dict[str, float]:
    """FLOPs and HBM bytes the attention calls of one train step need
    (forward + backward), from shapes alone. ``calls`` counts the
    forward pass's calls by kind; a kind that ends in "causal" needs
    half the FLOPs.

    One call, b x h heads of tq x tk x dh: forward is q.k^T and p.v,
    2 matmuls = 4*b*h*tq*tk*dh; backward needs dv, dp, dq, dk and the
    recomputed q.k^T of a flash kernel is NOT counted (recomputation),
    so 4 matmuls = 8*b*h*tq*tk*dh. A causal call needs half of each.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v,
    o, do and writes dq, dk, dv (the [b, tq, h] f32 logsumexp rows are
    1/dh of a tensor and left out)."""
    h = cfg["n_head"]
    dh = cfg["d_model"] // h
    flops = 0.0
    n_calls = 0
    for kind, n in calls.items():
        full = 12.0 * batch * h * seq * seq * dh
        flops += n * (full / 2 if kind.endswith("causal") else full)
        n_calls += n
    tensor = batch * seq * h * dh * bytes_per_el
    byts = n_calls * (4 + 8) * tensor
    return {"flops": flops, "bytes": float(byts), "calls": 2 * n_calls}
