"""What the Mamba-2 metrics share: the time of the Mamba-2 mixers in a
traced run, from the program's scopes (perf/spans.py:
``*/blk<i>/mamba2/`` with ``proj``, ``conv``, ``chunks``, ``gate_norm``
and ``out`` under it), the ``mamba2.*`` Mosaic kernels' self time
(perf/trace.py, by family) and the rows of the program's
``pt_mamba2_scan_dispatch_total`` and ``pt_moe_gmm_dispatch_total``
counters. A program without such a scope, kernel or counter (any tree
before the layer existed, any other family) has nothing to read: every
function here then returns None or nothing, and raises nothing."""

from perf import moe_spans, spans

COUNTER = "pt_mamba2_scan_dispatch_total"
GMM_COUNTER = "pt_moe_gmm_dispatch_total"
FAMILY = "mamba2"
# what of a mixer is the scan and not a projection: the convolution in
# front, the chunked scan, the gated norm behind
SCAN_PARTS = ("conv", "chunks", "gate_norm")


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/mamba2`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not mamba2_ns(s):
        return None
    return s


def mamba2_ns(s, *part):
    """Busy self time under ``*/blk*/mamba2/<part>`` (all of the mixer
    without one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("mamba2", *part))


def kernel_s(run):
    """Self seconds a chip of the ``mamba2.*`` Mosaic kernels in the
    traced stretch; 0.0 where the trace holds none."""
    return ((run.trace or {}).get("by_family_s") or {}).get(FAMILY, 0.0)


def _rows(counter):
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(counter, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows if r["value"]]


def dispatch_rows():
    """[(labels, calls)] of the Mamba-2 scan calls this process lowered
    with telemetry on (traced runs); [] where the program has no such
    counter or counted nothing."""
    return _rows(COUNTER)


def gmm_rows():
    """... of the experts' grouped matmuls."""
    return _rows(GMM_COUNTER)
