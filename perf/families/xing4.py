"""The Xing4.0 family (paddle_tpu.models.xing4): DeepSeek-V3's layers
(latent attention under a yarn table, leading dense layers, sigmoid-routed
experts beside an ungated shared one, an optional multi-token-prediction
module) on manifold-constrained hyper-connections: ``hc_mult`` residual
streams, a Sinkhorn-projected mix a token and a sublayer. A configuration
file carries the keys of the model's published ``config.json``;
``n_routed_experts`` is the experts THIS CHIP holds (``held_first`` on),
``router_experts`` the number the router scores."""

from perf import data
from perf.families.olmoe import packed_batch
from perf.flops_xing4 import mla_attention_cost, xing4_train_flops

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "first_k_dense_replace", "intermediate_size",
               "num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "rope_theta", "rope_scaling", "rms_norm_eps",
               "num_experts_per_tok", "moe_intermediate_size",
               "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
               "num_nextn_predict_layers", "hc_mult", "hc_sinkhorn_iters",
               "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. A dense layer
# and two expert layers; 4 of 16 experts held; the yarn table over 8
# original positions, so that the tests' 16 reach into the scaled waves.
TINY = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, moe_intermediate_size=16,
            n_routed_experts=4, router_experts=16, num_experts_per_tok=3,
            vocab_size=50, max_position_embeddings=16,
            rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 4,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 8,
                          "type": "yarn"})
# what the second check (reference/xing4.second_check) reads of the eval
# clone on the correctness sample: the logits of the last 8 positions,
# each expert layer's chosen experts and its rows per held expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")
# The state a run starts from (``build_graph``), laid over the builder's:
# at the builder's own state (gates alpha of 0.01, H_res's bias -8 off the
# diagonal) every H is nearly a constant of the layer, H_res nearly the
# identity whatever the iterations do, and ``correct`` could not tell a
# model that runs one Sinkhorn iteration, or none of the column steps,
# from this one (ROADMAP Queue 2 lesson (iv)). A trained model's mixes
# depend on the token. Here: every gate 1, so that a mix's pre-activations
# have the projection's own spread (about 2.4 at normal(0, 0.02) over
# 14,336 normalised features); H_res's bias drawn normal(0, 1) a sublayer,
# so that exp of it is far from doubly stochastic before the iterations;
# and the latent layers' second query projection (q_b) drawn at std 0.05
# where every other matrix has 0.02, as perf/families/kimilinear.py does
# and for its reason: at 0.02 the scores' std is under 1, the output a
# mean over thousands of values, and the softmax scale's mscale^2 and the
# yarn table move the logits by less than bf16 rounding does. (At 0.1 the
# scores' std is about 4: the program's own bf16 rounding then read
# 0.060-0.102 of the logits' rms over 19 seeds and 5.3-5.9% of the expert
# choices flipped, a spread too wide to set a limit over: my chip run,
# PR 67.)
HC_ALPHA = 1.0
HC_RES_BIAS_STD = 1.0
LATENT_QUERY_STD = 0.05


def program_config(cfg, **overrides):
    from paddle_tpu.models import xing4 as M

    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == cfg["topk_group"] == cfg["moe_layer_freq"] == 1
    assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(n_routed_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["n_routed_experts"]))
    kw.update(overrides)
    return M.Xing4Config(**kw)


def build_graph(pcfg, is_test=False):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.initializer import (ConstantInitializer,
                                        NormalInitializer,
                                        NumpyArrayInitializer)
    from paddle_tpu.models import xing4 as M

    model = M.build(pcfg, is_test=is_test)
    # (a second initializer op behind the builder's: the later write
    # stands, and the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    n = pcfg.hc_mult
    for name, var in list(startup.vars.items()):
        if name.endswith("_attn_q_b_colp.w"):
            NormalInitializer(0.0, LATENT_QUERY_STD)(var, startup)
        elif name.endswith("_hc.alpha"):
            ConstantInitializer(HC_ALPHA)(var, startup)
        elif name.endswith("_hc.bias"):
            # H_pre's and H_post's part as built; H_res's drawn: a
            # generator of its own a sublayer, from the parameter's name
            # and the program's seed, so that --seed decides it
            seed = sum(map(ord, name)) + int(
                fluid.default_startup_program().random_seed or 0)
            bias = np.concatenate([
                np.full(n, np.log(1.0 / max(n - 1, 1))), np.zeros(n),
                HC_RES_BIAS_STD * np.random.RandomState(
                    seed % (2 ** 31)).randn(n * n)])
            NumpyArrayInitializer(bias.astype("float32"))(var, startup)
    return model


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position. The MTP module's second
    targets (where a configuration runs it) are not tokens trained
    twice and are not counted."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return xing4_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """The latent-attention calls (a layer each, the MTP module's where
    it runs), causal, 192-wide queries and keys over 128-wide values."""
    return mla_attention_cost(cfg, batch, seq)
