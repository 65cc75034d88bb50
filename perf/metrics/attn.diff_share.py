"""Share of the device's busy self time under ``*/blk*/attn/diff/``:
what differential attention costs outside the attention kernels
(lambda, the subtraction of the two maps' outputs, the sub-norm and its
scale; forward and backward). None where the program has no such
scope."""

from perf import spans, ssm_spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    ns = ssm_spans.diff_ns(s)
    return 100.0 * ns / s["busy_ns"] if ns else None
