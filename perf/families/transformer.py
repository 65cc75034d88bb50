"""The encoder-decoder translation family (paddle_tpu.models.transformer)."""

from perf import data, flops

CONFIG_KEYS = ("src_vocab_size", "trg_vocab_size", "max_length", "d_model",
               "d_inner", "n_head", "n_layer", "dropout", "label_smooth_eps")
# the inference graph: no dropout, no label smoothing
SERVE_OVERRIDES = {"dropout": 0.0, "label_smooth_eps": 0.0}
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds
TINY = dict(d_model=32, d_inner=64, n_head=4, n_layer=2,
            src_vocab_size=50, trg_vocab_size=60, max_length=32)


def program_config(cfg, **overrides):
    from paddle_tpu.models import transformer as T

    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(overrides)
    return T.TransformerConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import transformer as T

    return T.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed, streams=2,     # source lengths, target lengths
        make_batch=lambda r, seq, sl, tl: data.transformer_batch(
            cfg, r, seq, sl, tl))


def real_tokens(feed):
    """Real (non-pad) target tokens."""
    return int(feed["trg_pad_mask"].sum())


def train_flops(cfg, batch, seq):
    return flops.transformer_train_flops(cfg, batch, seq, seq)


def attention_cost(cfg, batch, seq):
    n = cfg["n_layer"]
    return flops.attention_train_cost(
        {"enc_self": n, "dec_self_causal": n, "dec_cross": n}, cfg, batch,
        seq)
