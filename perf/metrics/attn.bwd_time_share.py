"""Share of the device's busy self time in the Pallas calls named
``attn.<family>.bwd*`` (parallel/flash_attention.py names every call
``attn.<family>.<pass>``); None where no kernel carries such a name."""

from perf import spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not s["kernel_ns"]:
        return None
    bwd = sum(v for k, v in s["kernel_ns"].items()
              if k.split(".")[2].startswith("bwd"))
    return 100.0 * bwd / s["busy_ns"]
