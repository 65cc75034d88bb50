"""The Phi-4-mini-flash family (paddle_tpu.models.phi4flash): Mamba-1
selective-scan layers beside sliding-window attention, one full
attention layer whose keys and values every later cross-attention layer
reads, gated memory units on one Mamba layer's scan output, differential
attention in pairs of heads, a tied table. A configuration file carries
the keys of the model's published ``config.json``; ``first_layer`` and
``model_layers`` say which of the published layers this chip holds (a
cut keeps the published indices), the ``mamba_*`` keys the sizes the
published file leaves to HF ``Phi4FlashConfig``'s defaults.

``attention_cost`` (and the attention term of ``train_flops``) is by
kind: a band for a window layer, a triangle for the full and for each
cross layer, two softmax maps a layer (perf/flops_phi4flash.py)."""

from perf import data, flops_phi4flash
from perf.families.olmoe import packed_batch

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads",
               "intermediate_size", "sliding_window", "layer_norm_eps",
               "mb_per_layer", "first_layer", "model_layers",
               "mamba_d_state", "mamba_d_conv", "mamba_expand",
               "mamba_dt_rank")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. 4 / 2 heads of
# 8 (two pairs over one), a window of 5 at the tests' 16 positions, 64
# channels of 4 states.
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, sliding_window=5, mamba_d_state=4,
            mamba_dt_rank=2, vocab_size=50, max_position_embeddings=16)
# what the second check (reference/phi4flash.second_check) reads of the
# eval clone on the correctness sample: the logits of the last 64
# positions
CHECK_FETCH = ("last_logits",)


def program_config(cfg, **overrides):
    from paddle_tpu.models import phi4flash as M

    assert cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu"
    assert not cfg["mlp_bias"] and not cfg["lm_head_bias"]
    assert not cfg["embd_pdrop"] and not cfg["resid_pdrop"]
    kw = {k: cfg[k] for k in CONFIG_KEYS if k in cfg}
    kw.update(overrides)
    return M.Phi4FlashConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import phi4flash as M

    return M.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: packed_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Next-token targets: every position."""
    return int(feed["labels"].size)


def train_flops(cfg, batch, seq):
    return flops_phi4flash.phi4flash_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """Two softmax maps a layer: a band each in a window layer, a
    triangle each in the full and the cross layers."""
    return flops_phi4flash.attention_cost(cfg, batch, seq)
