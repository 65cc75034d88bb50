"""The Granite-4.0-H family (paddle_tpu.models.granite_hybrid): layers
that are a mixer (a Mamba-2 layer whose 64 heads share ONE B / C group,
or an attention layer without positions at the softmax scale the config
states) and then a dense SwiGLU, under four scalar multipliers, a tied
head. A configuration file carries the keys of the model's published
``config.json`` (``layer_types`` whole, read by published index);
``first_layer`` says which of the published layers this chip holds,
``kernel_chunk`` the chunk the scan runs at (``mamba_chunk_size`` is kept
as published and not read: the chunk is where states are saved, not
mathematics), ``recompute`` which variables the builder marks as
checkpoints.

``attention_cost`` (and the attention term of ``train_flops``) counts
the attention layers alone, a triangle each (perf/flops_granitehybrid.py)."""

from perf import data, flops_granitehybrid
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "layer_types", "first_layer", "rms_norm_eps",
               "shared_intermediate_size", "embedding_multiplier",
               "attention_multiplier", "residual_multiplier",
               "logits_scaling", "mamba_n_heads", "mamba_d_head",
               "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
               "num_attention_heads", "num_key_value_heads", "recompute")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. Published
# layers 4 (Mamba-2), 5 (attention), 6 (Mamba-2); 4 Mamba-2 heads of 8 in
# ONE group over a state of 8, chunks of 8 at the tests' 16 positions;
# 4 / 2 attention heads of 8.
TINY = dict(hidden_size=32, first_layer=4, num_hidden_layers=3,
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8,
            kernel_chunk=8, num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, vocab_size=50,
            max_position_embeddings=16)
# what the second check (reference/granitehybrid.second_check) reads of
# the eval clone on the correctness sample: the last 128 positions' logits
CHECK_FETCH = ("last_logits",)
# The state a run starts from (``build_graph``): the attention layers'
# query and key columns at this many times the builder's normal(0, 0.02).
# At 0.02 a score q.k has a std of 6.5 and, at the stated scale of 1/64,
# the softmax is flat (std 0.1): its output is the mean of the values
# and no check can tell 1/64 from 1/8 (ROADMAP Queue 2, lesson (iv)). A
# trained layer attends sharply; at five times the std the scaled
# scores' std is about 2.5 and the second check sees the scale
# (perf/reference/granitehybrid.py has the readings).
QK_STD_FACTOR = 5.0


def program_config(cfg, **overrides):
    from paddle_tpu.models import granite_hybrid as M

    assert cfg["hidden_act"] == "silu" and cfg["tie_word_embeddings"]
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["normalization_function"] == "rmsnorm"
    assert cfg["num_local_experts"] == cfg["num_experts_per_tok"] == 0
    assert cfg["mamba_conv_bias"] and not (cfg["attention_bias"]
                                           or cfg["mamba_proj_bias"])
    kw = {k: cfg[k] for k in CONFIG_KEYS if k in cfg}
    kw.update(mamba_chunk_size=cfg["kernel_chunk"])
    kw.update(overrides)
    return M.GraniteHybridConfig(**kw)


def build_graph(pcfg, is_test=False):
    import paddle_tpu as fluid
    from paddle_tpu.models import granite_hybrid as M

    model = M.build(pcfg, is_test=is_test)
    # (ops behind the builder's initializers: the later write stands, and
    # the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    qk = (pcfg.num_attention_heads + pcfg.num_key_value_heads) * pcfg.head_dim
    v = pcfg.num_key_value_heads * pcfg.head_dim
    for name, var in list(startup.vars.items()):
        if name.endswith("_attn_qkv_colp.w"):
            factor = startup.create_var(name=f"{name}.start_factor",
                                        shape=[qk + v], dtype="float32")
            startup.append_op(
                "assign_value", outputs={"Out": factor},
                attrs={"shape": [qk + v], "dtype": "float32",
                       "values": [QK_STD_FACTOR] * qk + [1.0] * v})
            startup.append_op(
                "elementwise_mul", inputs={"X": var, "Y": factor},
                outputs={"Out": var}, attrs={"axis": -1})
    return model


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_granitehybrid.train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """One triangle an attention layer, 32 / 8 heads of 64."""
    return flops_granitehybrid.attention_cost(cfg, batch, seq)
