"""The Nemotron-H family (paddle_tpu.models.nemotron_h): blocks that are
ONE mixer each (a Mamba-2 layer in its chunked matmul form, an expert
layer of un-gated relu^2 experts behind a sigmoid router, or an
attention layer without positions), by a pattern string. A configuration
file carries the keys of the model's published ``config.json``;
``first_layer`` says which of the published blocks this chip holds (a
cut keeps the published indices), ``n_routed_experts`` is the experts
THIS CHIP holds (``held_first`` on, expert 0 on where the file has no
such key), ``router_experts`` the number the router scores.

``attention_cost`` (and the attention term of ``train_flops``) counts
the ``*`` blocks alone, a triangle each (perf/flops_nemotronh.py)."""

from perf import data, flops_nemotronh
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "hybrid_override_pattern", "first_layer",
               "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
               "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
               "time_step_min", "time_step_max", "num_attention_heads",
               "num_key_value_heads", "head_dim", "num_experts_per_tok",
               "moe_intermediate_size",
               "moe_shared_expert_intermediate_size", "norm_topk_prob",
               "routed_scaling_factor", "embedding_init_std")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. 4 Mamba-2
# heads of 8 in 2 groups over a state of 8, chunks of 8 at the tests' 16
# positions; 4 / 2 attention heads of 8; 2 of 8 experts held.
TINY = dict(hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
            ssm_state_size=8, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48, n_routed_experts=2,
            router_experts=8, num_experts_per_tok=3, vocab_size=50,
            max_position_embeddings=16)
# what the second check (reference/nemotronh.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 128
# positions, each expert layer's chosen experts and its rows per held
# expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import nemotron_h as M

    assert cfg["mlp_hidden_act"] == "relu2" and cfg["mamba_hidden_act"] == "silu"
    assert cfg["n_group"] == cfg["topk_group"] == cfg["n_shared_experts"] == 1
    assert cfg["use_conv_bias"] and not cfg["tie_word_embeddings"]
    assert not (cfg["attention_bias"] or cfg["mlp_bias"] or cfg["use_bias"]
                or cfg["mamba_proj_bias"])
    kw = {k: cfg[k] for k in CONFIG_KEYS if k in cfg}
    kw.update(n_routed_experts=cfg["router_experts"],
              held_experts=(cfg.get("held_first", 0),
                            cfg["n_routed_experts"]))
    kw.update(overrides)
    return M.NemotronHConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import nemotron_h as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_nemotronh.nemotronh_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """One triangle an attention block, 32 / 2 heads of 128."""
    return flops_nemotronh.attention_cost(cfg, batch, seq)
