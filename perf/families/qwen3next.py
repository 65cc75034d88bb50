"""The Qwen3-Next hybrid family (paddle_tpu.models.qwen3_next): Gated
DeltaNet layers beside gated softmax-attention layers, each with a
mixture of experts and a shared expert. A configuration file carries
the keys of the model's published ``config.json``; ``num_experts`` is
the experts THIS CHIP holds (``held_first`` on), ``router_experts`` the
number the router scores."""

from perf import data, flops
from perf.families.olmoe import packed_batch
from perf.flops_qwen3next import layer_kinds, qwen3next_train_flops

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "full_attention_interval", "num_attention_heads",
               "num_key_value_heads", "head_dim", "partial_rotary_factor",
               "rope_theta", "rms_norm_eps", "linear_conv_kernel_dim",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_num_key_heads", "linear_num_value_heads",
               "num_experts_per_tok", "moe_intermediate_size",
               "shared_expert_intermediate_size", "norm_topk_prob")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. Two chunks of
# the delta rule at the tests' 16 positions; 4 of 16 experts held.
TINY = dict(hidden_size=32, intermediate_size=64, head_dim=16,
            num_attention_heads=4, num_key_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=8,
            linear_num_key_heads=2, linear_num_value_heads=4,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_experts=4, router_experts=16, num_experts_per_tok=3,
            vocab_size=50, max_position_embeddings=16, gdn_chunk=8)
# what the second check (reference/qwen3next.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 8
# positions, each layer's chosen experts and its rows per held expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import qwen3_next as M

    assert not cfg["mlp_only_layers"] and cfg["decoder_sparse_step"] == 1
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(num_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["num_experts"]),
              gdn_chunk=cfg["gdn_chunk"])
    kw.update(overrides)
    return M.Qwen3NextConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import qwen3_next as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return qwen3next_train_flops(cfg, batch, seq, cfg["gdn_chunk"])


def attention_cost(cfg, batch, seq):
    """The full-attention layers' causal calls at their QUERY heads (K
    and V have fewer: their bytes are counted as if they had as many,
    and at these lengths the FLOP bound is the larger by far)."""
    h = cfg["num_attention_heads"]
    return flops.attention_train_cost(
        {"self_causal": layer_kinds(cfg)[1]},
        {"n_head": h, "d_model": h * cfg["head_dim"]}, batch, seq)
