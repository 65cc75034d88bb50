"""Share of the device's busy self time under the block-masked attention
calls (scopes ``*/blk*/attn/bd/``: the sdpa op of every layer trained by
block diffusion, forward and backward): the ``attn.bhtd.*`` kernels AND
whatever XLA puts around them there (a relayout, a copy of a half),
which ``attn.time_share.train``, the kernels alone, does not see. The
projections, the QK-norm and the rotation are not in it."""

from perf import bd_spans


def read(run):
    s = bd_spans.summary(run)
    return s and 100.0 * bd_spans.bd_ns(s) / s["busy_ns"]
