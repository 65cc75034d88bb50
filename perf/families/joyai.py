"""The JoyAI-LLM-Flash family (paddle_tpu.models.joyai_flash): latent
attention, a dense first layer, then sigmoid-routed experts beside an
ungated shared one, and a multi-token-prediction module. A
configuration file carries the keys of the model's published
``config.json`` (DeepSeek-V3's); ``n_routed_experts`` is the experts
THIS CHIP holds (``held_first`` on), ``router_experts`` the number the
router scores."""

from perf import data
from perf.families.olmoe import packed_batch
from perf.flops_joyai import joyai_train_flops, mla_attention_cost

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "first_k_dense_replace", "intermediate_size",
               "num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "rope_theta", "rms_norm_eps", "num_experts_per_tok",
               "moe_intermediate_size", "n_shared_experts", "norm_topk_prob",
               "routed_scaling_factor", "num_nextn_predict_layers")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. The dense
# layer and two expert layers beside the MTP module; 4 of 16 experts held.
TINY = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            qk_head_dim=24, v_head_dim=16, moe_intermediate_size=16,
            n_routed_experts=4, router_experts=16, num_experts_per_tok=3,
            vocab_size=50, max_position_embeddings=16)
# what the second check (reference/joyai.second_check) reads of the eval
# clone on the correctness sample: the main and the MTP logits of the
# last 8 positions, each expert layer's chosen experts and its rows per
# held expert
CHECK_FETCH = ("last_logits", "mtp_last_logits", "top_i", "expert_rows")


def program_config(cfg, **overrides):
    from paddle_tpu.models import joyai_flash as M

    assert cfg["scoring_func"] == "sigmoid" and cfg["rope_interleave"]
    assert cfg["topk_method"] == "noaux_tc" and cfg["rope_scaling"] is None
    assert cfg["n_group"] == cfg["topk_group"] == cfg["moe_layer_freq"] == 1
    assert (cfg["qk_head_dim"]
            == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(n_routed_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["n_routed_experts"]))
    kw.update(overrides)
    return M.JoyaiFlashConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import joyai_flash as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position. The MTP module's second
    targets are not tokens trained twice and are not counted."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return joyai_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """The six latent-attention calls (five layers and the MTP
    module's), causal, 192-wide queries and keys over 128-wide values."""
    return mla_attention_cost(cfg, batch, seq)
