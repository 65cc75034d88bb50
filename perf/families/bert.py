"""The BERT pretraining family (paddle_tpu.models.bert)."""

from perf import data, flops

CONFIG_KEYS = ("vocab_size", "max_position", "type_vocab_size", "d_model",
               "d_inner", "n_head", "n_layer", "dropout")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds
TINY = dict(d_model=32, d_inner=64, n_head=4, n_layer=2,
            vocab_size=50, max_position=16)


def program_config(cfg, **overrides):
    from paddle_tpu.models import bert as B

    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(overrides)
    return B.BertConfig(**kw)


def build_graph(pcfg, is_test=False):
    from paddle_tpu.models import bert as B

    return B.build(pcfg, is_test=is_test)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: data.bert_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """Real (non-pad) input tokens."""
    return int(feed["pad_mask"].sum())


def train_flops(cfg, batch, seq):
    return flops.bert_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    return flops.attention_train_cost({"enc_self": cfg["n_layer"]}, cfg,
                                      batch, seq)
