"""The Laguna family (paddle_tpu.models.laguna): sliding-window layers
of 64 query heads with plain rotary positions beside full layers of 48
under yarn over half a head, every head behind a sigmoid gate, a dense
SwiGLU in layer 0 and sigmoid-routed experts beside a shared one behind
it. A configuration file carries the keys of the model's published
``config.json``; ``num_experts`` is the experts THIS CHIP holds
(``held_first`` on), ``router_experts`` the number the router scores.

A family with more than one kind of attention layer writes
``attention_cost`` (and the attention term of ``train_flops``) by kind:
here a triangle at a full layer's heads and a band at a window layer's
(perf/flops_laguna.py), so that ``train_attn_roofline``,
``attn.time_share`` and ``step.mfu.train`` mean what they mean in the
other cells; ``swa.family_roofline.train`` finds ``swa_cost`` there by
the family's name."""

from perf import data, flops_laguna
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "head_dim", "rms_norm_eps",
               "num_experts_per_tok", "moe_intermediate_size",
               "shared_expert_intermediate_size",
               "moe_routed_scaling_factor", "sliding_window",
               "rope_parameters", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. UNEQUAL head
# counts for the two kinds (3 and 4 query heads a key/value head), a
# window of 5 at the tests' 16 positions, yarn over half a head of 16 (a
# base of 100 and betas that put the ramp over frequencies 0-5 of its 4:
# 0, 0.2, 0.4, 0.6), the dense layer and a whole period; 4 of 16 experts
# held.
TINY = dict(
    hidden_size=32, intermediate_size=64, head_dim=16,
    num_attention_heads=6, num_key_value_heads=2,
    num_attention_heads_per_layer=[6, 8, 8, 8, 6], sliding_window=5,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    num_experts=4, router_experts=16, num_experts_per_tok=3,
    vocab_size=50, max_position_embeddings=16,
    rope_parameters={
        "full_attention": {
            "rope_theta": 100, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 8, "beta_slow": 0.01,
            "beta_fast": 1, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}})
# what the second check (reference/laguna.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 64
# positions, each expert layer's chosen experts and its rows per held
# expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import laguna as M

    assert cfg["gating"] and not cfg["tie_word_embeddings"]
    assert not cfg["attention_bias"]
    assert not cfg["moe_apply_router_weight_on_input"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(num_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["num_experts"]))
    kw.update(overrides)
    return M.LagunaConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import laguna as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_laguna.laguna_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """One triangle at 48 heads a full layer, one band at 64 a window
    layer."""
    return flops_laguna.attention_cost(cfg, batch, seq)
