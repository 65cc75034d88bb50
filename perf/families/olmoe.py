"""The OLMoE decoder-only mixture-of-experts family
(paddle_tpu.models.olmoe). A configuration file carries the keys of the
model's published ``config.json``."""

from perf import data, flops
from perf.flops_olmoe import olmoe_train_flops

CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "num_hidden_layers", "num_attention_heads", "num_experts",
               "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
               "rope_theta")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds
TINY = dict(hidden_size=32, intermediate_size=16, num_attention_heads=4,
            num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
            vocab_size=50, max_position_embeddings=16)
# what the second check (reference/olmoe.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 8
# positions, each block's chosen experts and its rows per expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import olmoe as M

    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(overrides)
    return M.OlmoeConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import olmoe as M

    return M.build(pcfg, is_test=is_test)


def packed_batch(cfg, r, seq, lens):
    """Packed documents: every position is a real token (``lens`` are
    all ``seq``); the label of a position is the token after it."""
    assert (lens == seq).all(), "a packed batch has no padding"
    toks = r.randint(0, cfg["vocab_size"], (len(lens), seq + 1)).astype(
        "int64")
    return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return olmoe_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    return flops.attention_train_cost(
        {"self_causal": cfg["num_hidden_layers"]},
        {"n_head": cfg["num_attention_heads"], "d_model": cfg["hidden_size"]},
        batch, seq)
