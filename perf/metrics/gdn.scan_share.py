"""Of the Gated DeltaNet mixers' time (gdn.step_share.train), the share
under the ``conv``, ``rule`` and ``gate_norm`` scopes: the causal
convolution, the delta rule with its gates, and the gated norm, which
is everything that is not a projection matmul or the layer's norm."""

from perf import gdn_spans


def read(run):
    s = gdn_spans.summary(run)
    if not s:
        return None
    rest = sum(gdn_spans.gdn_ns(s, part)
               for part in gdn_spans.NOT_PROJECTION)
    return 100.0 * rest / gdn_spans.gdn_ns(s)
