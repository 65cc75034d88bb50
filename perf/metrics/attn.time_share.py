"""Share of device busy time in the attention kernels: the Mosaic
custom calls whose kernel name starts with ``attn.`` (device trace,
perf/trace.py ``by_family_s``). Another family's kernels (``moe.*``,
XLA's own ``ragged-dot-none``) are not attention and do not count."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"] or not t["by_family_s"].get("attn"):
        return None
    return 100.0 * t["by_family_s"]["attn"] / t["busy_s"]
