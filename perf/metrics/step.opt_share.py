"""Share of the device's busy self time under the ``opt/`` scope: what
Optimizer.apply_gradients appended (clip, regularizer, learning rate,
the update ops) and XLA did NOT fuse into a backward op (see
step.bwd_share)."""

from perf import spans


def read(run):
    return spans.share(run, lambda s: s["by_phase_ns"]["opt"])
