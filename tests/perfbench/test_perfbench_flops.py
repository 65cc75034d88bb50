"""perf/flops.py against numbers worked by hand."""

import pytest

from perf import flops, harness, models


def test_transformer_base_step_by_hand():
    cfg = harness.load_json("perf", "configs", "transformer-base.json")
    b, s = 128, 256
    tok = b * s
    d, di, v = 512, 2048, 10000
    proj = 2 * tok * d * d            # one d x d projection, forward
    ffn = 2 * 2 * tok * d * di
    attn = 2 * 2 * tok * s * d        # q.k^T and p.v
    enc = 6 * (4 * proj + ffn + attn)
    dec = 6 * (4 * proj + ffn + attn + 4 * proj + attn)
    fwd = enc + dec + 2 * tok * d * v
    assert flops.transformer_train_flops(cfg, b, s, s) == 3.0 * fwd
    # 0.33 GFLOP per position, forward and backward
    assert 3.0 * fwd / tok == pytest.approx(3.26e8, rel=0.01)


def test_bert_base_step_by_hand():
    cfg = harness.load_json("perf", "configs", "bert-base.json")
    b, t = 256, 128
    tok = b * t
    d, di, v = 768, 3072, 30522
    layer = 4 * 2 * tok * d * d + 2 * 2 * tok * d * di + 2 * 2 * tok * t * d
    fwd = 12 * layer + 2 * tok * d * d + 2 * tok * d * v
    assert flops.bert_train_flops(cfg, b, t) == 3.0 * fwd
    # about 0.66 GFLOP per token, forward and backward
    assert 3.0 * fwd / tok == pytest.approx(6.6e8, rel=0.02)


def test_attention_cost_counts_causal_calls_half():
    cfg = harness.load_json("perf", "configs", "transformer-base.json")
    b, s, h, dh = 128, 256, 8, 64
    full = 12.0 * b * h * s * s * dh      # fwd 4 + bwd 8, per call
    cost = models.family(cfg).attention_cost(cfg, b, s)
    assert cost["flops"] == 6 * full + 6 * full / 2 + 6 * full
    assert cost["calls"] == 36
    assert cost["bytes"] == 18 * 12 * b * s * h * dh * 2
    bert = harness.load_json("perf", "configs", "bert-base.json")
    assert models.family(bert).attention_cost(bert, 256, 128)["calls"] == 24
