"""The Kimi Linear hybrid family (paddle_tpu.models.kimi_linear): Kimi
Delta Attention layers beside latent-attention layers with no positional
embedding, a dense first layer, then sigmoid-routed experts beside an
ungated shared one. A configuration file carries the keys of the model's
published ``config.json`` (``linear_attn_config`` whole, its layers
numbered from 1); ``num_experts`` is the experts THIS CHIP holds
(``held_first`` on), ``router_experts`` the number the router scores."""

from perf import data
from perf.families.olmoe import packed_batch
from perf.flops_kimilinear import kimilinear_train_flops, mla_attention_cost

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "first_k_dense_replace", "intermediate_size",
               "num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "mla_use_nope", "rms_norm_eps", "linear_attn_config",
               "num_experts_per_token", "moe_intermediate_size",
               "num_shared_experts", "moe_renormalize",
               "routed_scaling_factor", "num_nextn_predict_layers",
               "kda_chunk")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. The dense
# layer under a KDA mixer, then KDA, KDA, latent attention with experts;
# two chunks of the rule at the tests' 16 positions; 4 of 16 experts held.
TINY = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            linear_attn_config={"full_attn_layers": [4],
                                "head_dim": 8, "kda_layers": [1, 2, 3],
                                "num_heads": 4, "short_conv_kernel_size": 4},
            moe_intermediate_size=16, num_experts=4, router_experts=16,
            num_experts_per_token=3, vocab_size=50, kda_chunk=8)
# what the second check (reference/kimilinear.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 8
# positions, each expert layer's chosen experts and its rows per held
# expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")
# The state a run starts from (``build_graph``): the latent layers' query
# projection drawn at this std where every other matrix has 0.02. At
# normal(0, 0.02) the one latent layer's scores have a std of 0.6 and its
# output is a mean over thousands of values: the float32 reference with
# the 64 shared features ROTATED then reads 0.0094-0.0099 of the logits'
# rms, UNDER the program's own bf16 rounding (0.0153-0.0163; my chip run,
# PR 64), and ``correct`` could not tell a model that quietly applies
# RoPE from this one. A trained layer attends sharply; at five times the
# std the scores' std is about 3 and the second check sees the rotation:
# on one seed the rotated reference read 0.065 at 0.06, 0.228 at 0.1 and
# 0.402 at 0.16 while the program read 0.0157, 0.0177 and 0.0237
# (perf/reference/kimilinear.py has the readings over the seeds).
LATENT_QUERY_STD = 0.1


def program_config(cfg, **overrides):
    from paddle_tpu.models import kimi_linear as M

    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["use_grouped_topk"] and cfg["rope_scaling"] is None
    assert cfg["num_expert_group"] == cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1 and not cfg["tie_word_embeddings"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(num_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["num_experts"]))
    kw.update(overrides)
    return M.KimiLinearConfig(**kw)


def build_graph(pcfg, is_test=False):
    import paddle_tpu as fluid
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import kimi_linear as M

    model = M.build(pcfg, is_test=is_test)
    # (a second initializer op behind the builder's: the later write
    # stands, and the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    for name, var in list(startup.vars.items()):
        if name.endswith("_attn_q_colp.w"):
            NormalInitializer(0.0, LATENT_QUERY_STD)(var, startup)
    return model


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return kimilinear_train_flops(cfg, batch, seq, cfg["kda_chunk"])


def attention_cost(cfg, batch, seq):
    """ONE triangle, the latent layer's alone: causal, 192-wide queries
    and keys over 128-wide values (the KDA layers' rule is no attention
    call: ``flops_kimilinear.kda_scan_cost``)."""
    return mla_attention_cost(cfg, batch, seq)
