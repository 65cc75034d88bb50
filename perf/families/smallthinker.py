"""The SmallThinker family (paddle_tpu.models.smallthinker): sliding-
window layers with rotary positions beside global layers without any,
grouped-query attention at 28 / 4 heads, a router that reads the
attention's input and ReGLU experts. A configuration file carries the
keys of the model's published ``config.json``;
``moe_num_primary_experts`` is the experts THIS CHIP holds
(``held_first`` on, expert 0 on where the file has no such key),
``router_experts`` the number the router scores.

A family with more than one kind of attention layer writes
``attention_cost`` (and the attention term of ``train_flops``) by kind:
here a triangle for each global layer and a band for each window layer
(perf/flops_smallthinker.py), so that ``train_attn_roofline``,
``attn.time_share`` and ``step.mfu.train`` mean what they mean in the
other cells."""

from perf import data, flops_smallthinker
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim",
               "rope_theta", "rms_norm_eps", "sliding_window_size",
               "sliding_window_layout", "rope_layout",
               "moe_num_active_primary_experts", "moe_ffn_hidden_size",
               "norm_topk_prob")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. A window of 5
# at the tests' 16 positions; 7 query heads a key/value head; 2 of 8
# experts held.
TINY = dict(hidden_size=32, head_dim=8, num_attention_heads=7,
            num_key_value_heads=1, sliding_window_size=5,
            moe_ffn_hidden_size=16, moe_num_primary_experts=2,
            router_experts=8, moe_num_active_primary_experts=3,
            vocab_size=50, max_position_embeddings=16)
# what the second check (reference/smallthinker.second_check) reads of
# the eval clone on the correctness sample: the logits of the last 64
# positions, each layer's chosen experts and its rows per held expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import smallthinker as M

    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    assert cfg["rope_scaling"] is None and not cfg["tie_word_embeddings"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(moe_num_primary_experts=cfg["router_experts"],
              held_experts=(cfg.get("held_first", 0),
                            cfg["moe_num_primary_experts"]))
    kw.update(overrides)
    return M.SmallThinkerConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import smallthinker as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_smallthinker.smallthinker_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """One triangle a global layer, one band a window layer."""
    return flops_smallthinker.attention_cost(cfg, batch, seq)
