"""Share of the device's busy self time in ops whose ``tf_op`` carries
one of the program's phase scopes (fwd/, bwd/, opt/: core/interp
``exec_ops``): how much of the device's time the program can name. The
rest is what XLA adds outside any op (copies, layout changes) and
fusions that took the metadata of an unscoped root."""

from perf import spans


def read(run):
    return spans.share(run, lambda s: s["scoped_ns"])
