"""The LFM2-MoE family (paddle_tpu.models.lfm2_moe): blocks whose
sequence mixer is a gated short convolution or, every fourth, a
grouped-query attention layer with per-head QK-norm, read from the
``layer_types`` list; the first ``num_dense_layers`` blocks carry a
dense SwiGLU, the others sigmoid-routed SwiGLU experts. A configuration
file carries the keys of the model's published ``config.json``;
``first_layer`` says which of the published blocks this chip holds (a
cut keeps the published indices and the whole ``layer_types``),
``num_experts`` is the experts THIS CHIP holds (``held_first`` on,
expert 0 on where the file has no such key), ``router_experts`` the
number the router scores.

``attention_cost`` (and the attention term of ``train_flops``) counts
the ``full_attention`` blocks alone, a triangle each
(perf/flops_lfm2moe.py).

**The state a run starts from.** A fresh LFM2 holds two of its
mechanisms where they do nothing, or nearly: ``expert_bias`` at 0, where
a choice that ignores it is the same choice, and the per-head QK-norms'
gains at 1 over q and k of rms 0.9, where dropping the norm moves the
logits by 0.7-1.0% of their rms (my chip run, PR 48), inside the second
check's limits. ``correct`` is read before the first step, so
``build_graph`` lays over both, in the startup program, what a trained
model holds: the gains at normal(``QK_GAIN``) from the seed (the scores
four times sharper), the bias at +-``SELECT_BIAS`` by the expert's
parity (NOT drawn: the experts' time follows the rows they get, a bias
of normal(0, 0.05) read 10.1% and 13.6% of the pairs on the held experts
on two seeds, and an expert range of even length holds as many of each
sign). The training run starts there too; the bias's own rule moves it
on from the first step. PERF.md section 4."""

from perf import data, flops_lfm2moe
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "num_hidden_layers", "layer_types", "first_layer",
               "num_dense_layers", "norm_eps", "conv_L_cache", "conv_bias",
               "num_attention_heads", "num_key_value_heads",
               "num_experts_per_tok", "moe_intermediate_size",
               "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. 4 / 2
# attention heads of 8; 2 of 8 experts held, 3 a token; the file's five
# blocks (1-5: the dense layer and one whole period) stay.
TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=16, num_experts=2,
            router_experts=8, num_experts_per_tok=3, vocab_size=50,
            max_position_embeddings=16)
# what the second check (reference/lfm2moe.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 64
# positions, each expert layer's chosen experts and its rows per held
# expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")
QK_GAIN = (2.0, 0.2)   # mean, std of every q / k norm's gains
SELECT_BIAS = 0.03     # |expert_bias|, + on the even experts


def program_config(cfg, **overrides):
    from paddle_tpu.models import lfm2_moe as M

    assert cfg["model_type"] == "lfm2_moe" and not cfg["conv_bias"]
    assert cfg["rope_parameters"]["rope_type"] == "default"
    assert len(cfg["layer_types"]) == cfg["model_layers"]
    kw = {k: cfg[k] for k in CONFIG_KEYS if k in cfg}
    kw.update(rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
              num_experts=cfg["router_experts"],
              held_experts=(cfg.get("held_first", 0), cfg["num_experts"]))
    kw.update(overrides)
    return M.Lfm2MoeConfig(**kw)


def build_graph(pcfg, is_test=False):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.initializer import (NormalInitializer,
                                        NumpyArrayInitializer)
    from paddle_tpu.models import lfm2_moe as M

    model = M.build(pcfg, is_test=is_test)
    # (a second initializer op behind the builder's: the later write
    # stands, and the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    for name, var in list(startup.vars.items()):
        if name.endswith(("_qnorm.scale", "_knorm.scale")):
            NormalInitializer(*QK_GAIN)(var, startup)
        elif name.endswith("_router.bias"):
            NumpyArrayInitializer(np.resize(
                [SELECT_BIAS, -SELECT_BIAS], var.shape))(var, startup)
    return model


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_lfm2moe.lfm2moe_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """One triangle an attention block, 32 / 8 heads of 64."""
    return flops_lfm2moe.attention_cost(cfg, batch, seq)
