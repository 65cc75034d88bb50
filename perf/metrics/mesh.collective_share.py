"""Share of device busy time spent in collective operations (device
trace, averaged over the chips)."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["by_kind_s"].get("collective", 0.0) / t["busy_s"]
