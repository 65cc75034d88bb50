"""Share of the device's busy self time in the Pallas calls named
``attn.<what>.bwd*`` (parallel/flash_attention.py names every call
``attn.<what>.<pass>``); None where no kernel carries such a name.
``kernel_ns`` holds every family's kernels (a later ``moe.*``): only
the ``attn`` family counts here."""

from perf import spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    attn = {k: v for k, v in s["kernel_ns"].items()
            if k.startswith("attn.")}
    if not attn:
        return None
    bwd = sum(v for k, v in attn.items()
              if k.split(".")[2].startswith("bwd"))
    return 100.0 * bwd / s["busy_ns"]
