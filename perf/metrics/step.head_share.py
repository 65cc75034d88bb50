"""Share of the device's busy self time under the name scope
``loss_head`` (transformer) or ``mlm_head`` (BERT), forward and
backward together: the vocabulary projection and its loss."""

from perf import spans


def read(run):
    return spans.share(run, lambda s: s["head_ns"])
