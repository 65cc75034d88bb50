"""Share of the device's busy self time in the multi-token-prediction
module (scopes ``*/blk_mtp/`` and ``*/loss_head/mtp/``: the merge of the
hidden state with the next token's embedding, one more block, and the
second pass through the model's own head; forward and backward)."""

from perf import mla_spans


def read(run):
    s = mla_spans.summary(run)
    return s and 100.0 * mla_spans.mtp_ns(s) / s["busy_ns"]
