"""The Keye family (paddle_tpu.models.keye): a Qwen3-MoE block (grouped-
query attention at 32 / 4 heads with per-head QK-norm, 128 softmax-routed
SwiGLU experts of 768, 8 a token) whose attention reads, for every query,
the 2048 keys a lightning indexer chose (DeepSeek Sparse Attention), the
indexer learning from a KL loss of its own, under multi-axis rotary
positions that are FED. A configuration file carries the keys of the
model's published ``config.json`` (``sa_config`` and ``rope_scaling``
whole); ``num_experts`` is the experts THIS CHIP holds (``held_first``
on), ``router_experts`` the number the router scores.

**The feed.** ``input_ids``, ``labels`` (packed tokens, the next token a
position) and ``position_ids`` [3, t]: text rows, three equal rows
0 .. t - 1.

**The state a run starts from.** A fresh indexer at normal(0, 0.02)
scores noise: I is some 1e-4 wide, its top-k a draw of bf16's rounding,
and ``correct`` could not tell the program's selection from any other.
The sparse stage starts from an indexer that a warm-up stage has
trained; ``build_graph`` lays over the builder's fresh model, in the
startup program, the indexer's three matrices at normal(0,
``INDEX_STD``): scores of order one (I's spread over a row's keys is
about 2, L_I about 3 against the cross entropy's 9.85), so that a
query's 2048 are a function of the weights which bf16's rounding moves
only at the few keys near the threshold (0.4% of a row in the first
layer: my chip run, PR 71). It is this file's own, said so in the
configuration's ``assumed``. The per-head QK-norm gains are laid at
normal(``QK_GAIN``): a MEAN of 1, the builder's, because at
``perf/families/sdar.py``'s normal(2, 0.2) the scores are four times
sharper, a query's output is a few keys' values, and every key that
bf16 moves across the indexer's threshold moves it: the selections of
program and float32 reference then part layer by layer (0.4%, 2.1%,
8.4%, 20.5% of a row's keys in layers 0..3, 13% of the expert choices,
0.25 of the logits' rms: my chip run, PR 71), which is chaos, not
rounding, and no limit could tell it from a fault; and a SPREAD of 0.3 a
feature, a trained norm's, because at gains of exactly 1 on fresh
projections the norm only rescales the scores by about 0.8 and a
program without it would read as correct. And every router's columns
are brought to ONE length, ``ROUTER_STD`` sqrt(d), ten fresh columns',
as ``perf/families/sdar.py`` does and for its reason: pairs on experts
held elsewhere are computed by nobody here, so the held sixteen are the
only experts whose weight lowers the loss, a router of fresh length
learns that inside the window (the harness's Adam moves an entry by up
to 1e-4 a step, 0.4% of a fresh entry's size a step), the held experts'
rows and the step's time climb (`window.step_drift.train` 1.15 over 32
steps, the traced steps behind the window 704 ms for the window's mean
of 658: my chip run, PR 71) and where they stop is a draw of the seed:
an artefact of the cut, which a deployment's router, seeing every
expert's pairs, does not have. The choice of the 8 does not go by the
length, so the first step's routing is the same.

``attention_cost`` (and the attention term of ``train_flops``) counts the
SELECTED pairs, min(p + 1, 2048) a query (perf/flops_keye.py)."""

import numpy as np

from perf import data, flops_keye
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim",
               "rope_theta", "rms_norm_eps", "num_experts_per_tok",
               "moe_intermediate_size", "norm_topk_prob")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. 4 query heads
# a key/value head; 2 of 8 experts held, 3 a token; 2 index heads of 8,
# 6 keys a query in the tests' rows of 16 (rows below and above k), tiles
# of 8.
TINY = dict(hidden_size=32, head_dim=8, num_attention_heads=8,
            num_key_value_heads=2, moe_intermediate_size=16, num_experts=2,
            router_experts=8, num_experts_per_tok=3, vocab_size=50,
            max_position_embeddings=16,
            rope_scaling={"mrope_section": [1, 1, 2], "rope_type": "default",
                          "type": "default"},
            sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                       "q_chunk_size": 8, "topk": 6},
            indexer_rope_dim=4)
# what the second check (reference/keye.second_check) reads of the eval
# clone on the correctness sample: the logits of the last 64 positions,
# those rows of every layer's selection, each layer's chosen experts and
# its rows per held expert
CHECK_FETCH = ("last_logits", "last_selected", "top_i", "expert_rows")
QK_GAIN = (1.0, 0.3)   # mean, std of every q / k norm's gains
ROUTER_STD = 0.2       # a router's entries: its columns' length / sqrt(d)
INDEX_STD = 0.04       # the indexer's three matrices' entries


def program_config(cfg, **overrides):
    from paddle_tpu.models import keye as M

    sa, rs = cfg["sa_config"], cfg["rope_scaling"]
    assert cfg["model_type"] == "KeyeVL2" and cfg["norm_topk_prob"]
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert rs["rope_type"] == "default" and not cfg["mlp_only_layers"]
    assert cfg["decoder_sparse_step"] == 1 and not cfg["use_sliding_window"]
    assert sa["indexer_num_kv_heads"] == 1
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(num_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["num_experts"]),
              mrope_section=rs["mrope_section"],
              indexer_num_heads=sa["indexer_num_heads"],
              indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
              q_chunk_size=sa["q_chunk_size"],
              kv_chunk_size=sa["kv_chunk_size"],
              indexer_rope_dim=cfg.get("indexer_rope_dim",
                                       sa["indexer_head_dim"] // 2))
    kw.update(overrides)
    return M.KeyeConfig(**kw)


def build_graph(pcfg, is_test=False):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import keye as M

    model = M.build(pcfg, is_test=is_test)
    # (a second initializer op behind the builder's: the later write
    # stands, and the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    for name, var in list(startup.vars.items()):
        if name.endswith(("_attn_qnorm.scale", "_attn_knorm.scale")):
            NormalInitializer(*QK_GAIN)(var, startup)
        if name.endswith(("_idx_q.w", "_idx_k.w", "_idx_w.w")):
            NormalInitializer(0.0, INDEX_STD)(var, startup)
    with fluid.program_guard(fluid.default_startup_program()):
        for name in list(startup.vars):
            if name.endswith("_moe_router.w"):
                # every column of ONE length, ROUTER_STD sqrt(d)
                w = startup.var(name)
                length = layers.pow(layers.reduce_sum(
                    layers.elementwise_mul(w, w), dim=0, keep_dim=True), -0.5)
                layers.assign(layers.elementwise_mul(w, layers.scale(
                    length, scale=ROUTER_STD * pcfg.hidden_size ** 0.5)),
                    output=w)
    return model


def text_batch(cfg, r, seq, lens):
    """Packed tokens and a text row's positions: three equal rows."""
    feed = packed_batch(cfg, r, seq, lens)
    feed["position_ids"] = np.tile(np.arange(seq, dtype=np.int64), (3, 1))
    return feed


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: text_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_keye.keye_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """The selected pairs, min(p + 1, topk) a query, head and layer."""
    return flops_keye.attention_cost(cfg, batch, seq)
