"""Share of the device's busy self time under the ``bwd/`` scope: the
ops backward.append_backward emitted (raw trace, perf/spans.py). A
fusion counts where its root's op_name puts it: an optimizer update
XLA fused into a weight gradient's matmul counts here, not under
``opt/``."""

from perf import spans


def read(run):
    return spans.share(run, lambda s: s["by_phase_ns"]["bwd"])
