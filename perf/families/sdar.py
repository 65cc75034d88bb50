"""The SDAR family (paddle_tpu.models.sdar): a Qwen3-MoE block (grouped-
query attention at 32 / 4 heads with per-head QK-norm, 128 softmax-routed
SwiGLU experts of 768, 8 a token) trained by BLOCK DIFFUSION: the network
runs once over a row of [noised copy ; clean copy] under the three-part
block mask, and the loss reads the masked positions of the noised copy,
each weighted by 1 / p of its block. A configuration file carries the
keys of the model's published ``config.json`` plus ``block_length`` and
``mask_token_id``; ``num_experts`` is the experts THIS CHIP holds
(``held_first`` on), ``router_experts`` the number the router scores.

**The feed.** A cell's ``seq_len`` is L, the DATA tokens of a row; the
row the network sees is 2 L positions. ``feeds`` draws, from ``--seed``,
x0 [b, L] below the mask id, one noise level a block and the masks
(``models/sdar.noise``), and hands over ``input_ids`` [b, 2 L] = [xt ;
x0], ``labels`` [b, L] (x0 where xt is the mask id, ``ignore_index``
elsewhere) and ``loss_weight`` [b, L] (1 / p): the noise is the feed's,
so the reference sees the very row the program saw. ``real_tokens`` is
b x L: what ``train_tokens_per_s`` counts is data tokens, neither the
2 L positions nor the masked count.

**The state a run starts from.** A conversion to block diffusion starts
from a TRAINED autoregressive checkpoint: rows of the table that differ
from token to token, attention that looks at a few positions, and routers
that a balancing loss has held level over everything its positions
share. ``build_graph`` lays that over the builder's fresh model, in the
startup program, because a fresh model's routing makes the step's time a
draw of the seed: the mask token is a quarter of the row's positions and
its rows are all alike, so every masked position of a fresh layer chooses
the same 8 experts, whether this chip holds one of them is one draw a
layer and seed, and the held experts' rows a step, which the step's time
follows, swing from seed to seed. Three parts, each measured on the chip
at the published widths with the others in place and with it dropped
(``perf/tools/sdar_start_states.py``, six seeds a state; the readings are
in PERF.md section 6, PR 61, and a new cell is admitted under a spread of
0.5%):

- the table at normal(0, ``TABLE_STD``), the mask token's row with it
  (torch's ``nn.Embedding`` default, as ``models/smallthinker.py`` says
  of its own): a position's state is its own token's row first;
- the per-head QK-norm gains at normal(``QK_GAIN``) from the seed, as
  ``perf/families/lfm2moe.py`` does: at gains of 1 an untrained layer's
  scores are N(0, 1) and a query's context is the mean of a third of
  what it sees, nearly the same for every late position, so masked
  positions still route alike; at 2 the scores are four times sharper
  and a context is a few positions' values. The second check pays for
  it: bf16's rounding of the sharper scores is 5% of the logits' rms
  where it is 0.4% at gains of 1, and its limits stand that much higher
  (``perf/reference/sdar.py``);
- every router's columns made ORTHOGONAL to the mask token's row (W <-
  W - m^T (m W) / (m m^T), a function of the drawn weights, nothing
  drawn), so that a masked position routes by what its context adds to
  the row and not by the one vector they all share, and brought to ONE
  length, ``ROUTER_STD`` sqrt(d): which experts a fresh router favours
  goes by its columns' lengths (1.6% apart at d 2048), and a balancing
  loss has levelled that in a trained one. The length is TEN fresh
  columns' (0.2 where the builder draws at 0.02). The choice of the 8
  does not go by the length, so the first step's routing is the same;
  what the length sets is how far the run itself turns a router. The
  harness's Adam moves an entry by at most its learning rate a step,
  1e-4, whatever the entry's size: over the 125 steps of a run that is
  0.0125, 62% of a fresh entry and 6% of one at 0.2. Pairs on experts
  held elsewhere are computed by nobody here, so the only experts whose
  weight lowers the loss are the sixteen held, and a router of fresh
  length learns that inside the window: the held experts' rows and the
  step's time climb (170 -> 182-184 ms in twenty steps) and where they
  stop is a draw of the seed. That climb is an artefact of the cut (a
  deployment's router sees every expert's pairs), so the state is one
  the run does not rewrite. No public source gives a trained router's
  length; 0.2 is this file's, said so in the configuration's
  ``assumed``, and 0.1 (a third of the climb) and 0.3 (none) were read
  beside it. With gains and table in place the orthogonal columns and
  the one length are each worth less than six seeds resolve (0.36% and
  0.34% without, 0.30% with, in one call): they stay for their reasons
  and are the first to go if a later reading finds them idle.

``attention_cost`` (and the attention term of ``train_flops``) counts
the block mask's live pairs exactly, L^2 + B L a head and layer
(perf/flops_sdar.py)."""

import numpy as np

from perf import data, flops_sdar

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim",
               "rope_theta", "rms_norm_eps", "num_experts_per_tok",
               "moe_intermediate_size", "norm_topk_prob", "block_length",
               "mask_token_id")
# the family's sizes for the CPU tests (tests/perfbench/perfbench_tiny):
# laid over a configuration file, they compile in seconds. 8 query heads
# a key/value head; 2 of 8 experts held, 3 a token; blocks of 4 in the
# tests' rows of 16 data tokens (32 positions: no multiple of any tile);
# the mask id the last of 50.
TINY = dict(hidden_size=32, head_dim=8, num_attention_heads=8,
            num_key_value_heads=1, moe_intermediate_size=16, num_experts=2,
            router_experts=8, num_experts_per_tok=3, vocab_size=50,
            mask_token_id=49, block_length=4, max_position_embeddings=16)
# what the second check (reference/sdar.second_check) reads of the eval
# clone on the correctness sample: the logits of the noised half's last
# 256 positions, each layer's chosen experts and its rows per held expert
CHECK_FETCH = ("last_logits", "top_i", "expert_rows")
QK_GAIN = (2.0, 0.2)   # mean, std of every q / k norm's gains
TABLE_STD = 1.0        # every row of the embedding table
ROUTER_STD = 0.2       # a router's entries: its columns' length / sqrt(d)


def program_config(cfg, **overrides):
    from paddle_tpu.models import sdar as M

    assert cfg["model_type"] == "sdar_moe" and cfg["norm_topk_prob"]
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["rope_scaling"] is None and not cfg["mlp_only_layers"]
    assert cfg["decoder_sparse_step"] == 1 and not cfg["use_sliding_window"]
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw.update(num_experts=cfg["router_experts"],
              held_experts=(cfg["held_first"], cfg["num_experts"]))
    kw.update(overrides)
    return M.SdarConfig(**kw)


def build_graph(pcfg, is_test=False):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import sdar as M

    model = M.build(pcfg, is_test=is_test, embedding_init_std=TABLE_STD)
    # (a second initializer op behind the builder's: the later write
    # stands, and the draws in front of it stay what they were)
    startup = fluid.default_startup_program().global_block()
    for name, var in list(startup.vars.items()):
        if name.endswith(("_qnorm.scale", "_knorm.scale")):
            NormalInitializer(*QK_GAIN)(var, startup)
    with fluid.program_guard(fluid.default_startup_program()):
        table = startup.var(M.TABLE)
        at = layers.assign(np.array([pcfg.mask_token_id], np.int64))
        row = layers.gather(table, at)                          # m [1, d]
        inv = layers.pow(layers.matmul(row, row, transpose_y=True), -1.0)
        for name in list(startup.vars):
            if name.endswith("_moe_router.w"):
                w = startup.var(name)
                shared = layers.matmul(row, layers.matmul(row, w),
                                       transpose_x=True)        # m^T (m W)
                level = layers.elementwise_sub(
                    w, layers.elementwise_mul(shared, inv))
                # every column of ONE length, ROUTER_STD sqrt(d)
                length = layers.pow(layers.reduce_sum(
                    layers.elementwise_mul(level, level), dim=0,
                    keep_dim=True), -0.5)
                layers.assign(layers.elementwise_mul(level, layers.scale(
                    length, scale=ROUTER_STD * pcfg.hidden_size ** 0.5)),
                    output=w)
    return model


def noised_batch(cfg, r, seq, lens):
    """Packed documents: every one of the ``seq`` data tokens a row is
    real (``lens`` are all ``seq``), drawn below the mask id, then
    noised by block (``models/sdar.noise``)."""
    from paddle_tpu.models import sdar as M

    assert (lens == seq).all(), "a packed batch has no padding"
    x0 = r.randint(0, cfg["mask_token_id"], (len(lens), seq))
    return M.noise(x0, cfg["block_length"], cfg["mask_token_id"], r)


def feeds(cfg, traffic, seed):
    return data.train_feeds(
        traffic, seed,
        make_batch=lambda r, seq, lens: noised_batch(cfg, r, seq, lens))


def real_tokens(feed):
    """The row's DATA tokens, b x L: neither the 2 L positions the
    network sees nor the masked ones the loss reads."""
    return int(np.asarray(feed["labels"]).size)


def train_flops(cfg, batch, seq):
    return flops_sdar.sdar_train_flops(cfg, batch, seq)


def attention_cost(cfg, batch, seq):
    """The block mask's live pairs, L^2 + B L a head and layer."""
    return flops_sdar.attention_cost(cfg, batch, seq)
