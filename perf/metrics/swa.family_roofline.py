"""The windowed attention calls' share of their roofline, for a family
that counts its own window layers: the least time the chip could take
for one step's sliding-window calls, the larger of FLOPs over the bf16
peak and bytes over the HBM peak, over the device's self time a step
under the ``*/blk*/attn/swa/`` scopes. The cost is
``perf/flops_<family>.swa_cost(config, batch, seq)``, found through the
run's family (the configuration file's ``family``), the BAND counted by
elements at the window layers' OWN head count, forward + 2 x backward,
against q, k, v, o and their gradients moved once: the next family with
window layers of its own lists its cell and adds no reader.
(``swa.roofline.train`` reads ``flops_smallthinker.swa_cost``, one head
count for every layer.)

A perfect kernel reads under 100 (whole blocks, the backward's
recomputed q.k^T, the softmax on the VPU), and at a window equal to one
block far under: with a window of 512 on blocks of 512 every row of
query blocks but the first walks TWO key blocks, each cut by an edge of
the band, 8192 x 1024 pairs a head at 8192 positions for the band's
4.06M: 48% is the ceiling of laguna-train-s8192's calls however good
the kernel (PERF.md section 7).

None where the family has no ``flops_<family>`` module or no
``swa_cost`` in it, or the trace holds no ``swa`` scope."""

import importlib

from perf import harness, swa_spans


def family_swa_cost(run):
    """``swa_cost`` of perf/flops_<family>.py, None where there is none."""
    try:
        mod = importlib.import_module(
            f"perf.flops_{run.config.get('family')}")
    except ImportError:
        return None
    return getattr(mod, "swa_cost", None)


def read(run):
    w = run.window
    s = swa_spans.summary(run)
    swa_cost = family_swa_cost(run)
    if not s or not w.get("traced_steps") or swa_cost is None:
        return None
    swa_s = swa_spans.swa_ns(s) / 1e9 / s["chips"]
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = swa_cost(run.config, traffic["batch"] // run.cell["chips"],
                    traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / swa_s
