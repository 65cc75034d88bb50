"""Share of the device's busy self time in the state-space mixers and
the gated memory units that read one of them (scopes ``*/blk*/ssm/``:
norm, projections, convolution, the selective scan, gate, output
projection; and ``*/blk*/gmu/``; forward and backward)."""

from perf import ssm_spans


def read(run):
    s = ssm_spans.summary(run)
    return s and 100.0 * (ssm_spans.ssm_ns(s) + ssm_spans.gmu_ns(s)) \
        / s["busy_ns"]
