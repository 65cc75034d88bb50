"""Operations and bytes of the Xing4.0 family (latent attention, leading
dense layers, sigmoid-routed experts beside a shared one, an optional
multi-token-prediction module: perf/flops_joyai.py counts those) under
manifold-constrained hyper-connections: n residual streams and, around
every sublayer, a mix, a read and a write-back (paddle_tpu/ops/hc_ops.py).
From shapes alone (the conventions of perf/flops.py: a multiply-add
counts 2, recomputation does not count, embedding lookups are left
out)."""

from __future__ import annotations

from typing import Dict

from perf.flops_joyai import joyai_train_flops, mla_attention_cost, mla_blocks

__all__ = ["xing4_train_flops", "hc_stream_cost", "hc_sublayers",
           "mla_attention_cost"]


def hc_sublayers(cfg: Dict) -> int:
    """Hyper-connected sublayers of the stack: an attention and a
    feed-forward a layer, the MTP module's two with it."""
    return 2 * mla_blocks(cfg)


def hc_flops_per_token(cfg: Dict) -> float:
    """Forward FLOPs a token of ONE sublayer's hyper-connection: the
    mix's projection [n d] -> n^2 + 2n, the read's n products a feature
    and the write-back's n^2 + n (the Sinkhorn iterations, a few hundred
    operations a token, are not counted)."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return float(2 * n * d * (n * n + 2 * n) + 2 * n * d
                 + 2 * (n * n + n) * d)


def xing4_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: ``flops_joyai.joyai_train_flops`` (the
    latent attention, the dense SwiGLU, the expected held share of the
    routed experts, the head; the MTP module where the configuration
    runs it) and every sublayer's hyper-connection. backward = 2 x
    forward."""
    return (joyai_train_flops(cfg, batch, t)
            + 3.0 * batch * t * hc_sublayers(cfg) * hc_flops_per_token(cfg))


def hc_stream_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                   ) -> Dict[str, float]:
    """HBM bytes the stream passes of one train step must move, whatever
    implements them: the count is of the WORK. A sublayer F sits between
    its read and its write-back, forward and backward, so each way is two
    passes, and a pass reads or writes each of its stream-sized tensors
    once (X, X', dX, dX': n rows of d a token; h, y, dh, dy: one):

        forward   pass 1 (statistic, projection, read):  read X, write h
                  pass 2 (write-back):        read X and y, write X'
                                                       (3n + 2) t d
        backward  pass 3 (in front of F's backward): read X, y, dX',
                  write dy (dH_res and dH_post are a few numbers a token)
                  pass 4 (behind it): read X, dX', dh, write dX = H_res^T
                  dX' + H_pre dh + the mix's own (the statistic's and the
                  projection's)                        (5n + 3) t d

    (8n + 5) t d elements a sublayer: 37 x 4096 x 3584 x 2 bytes = 1.09 GB
    at n 4, of which the forward is 0.41 GB. What is NOT counted: the mixes
    themselves (24 float32 a token), Phi and its gradient, a gradient
    accumulated over more than one producer, a second read of anything.
    FLOPs: ``hc_flops_per_token``, forward + 2 x backward."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    subs, tok = hc_sublayers(cfg), batch * t
    return {"flops": 3.0 * tok * subs * hc_flops_per_token(cfg),
            "bytes": float(subs * (8 * n + 5) * tok * d * bytes_per_el),
            "forward_bytes": float(subs * (3 * n + 2) * tok * d
                                   * bytes_per_el),
            "calls": 6 * subs}
