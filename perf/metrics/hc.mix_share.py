"""Share of the device's busy self time under ``hc/mix``: the norm
statistic, the [n d] -> n^2 + 2n projection, the sigmoids and the
Sinkhorn iterations of every sublayer's mix, forward and (made again,
then walked back) backward: the latency-bound part of the
hyper-connections."""

from perf import hc_spans


def read(run):
    s = hc_spans.summary(run)
    return s and 100.0 * hc_spans.hc_ns(s, "mix") / s["busy_ns"]
