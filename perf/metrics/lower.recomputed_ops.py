"""Forward ops that backward.append_backward appended again for the
backward pass (``pt_backward_recompute_ops_total``, summed over the
segments; it counts only with telemetry on, that is in traced runs).
None where the program marks no checkpoint."""

from perf import recompute_spans


def read(run):
    return recompute_spans.replayed_ops()
