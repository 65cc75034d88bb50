"""Share of device busy time in the Pallas attention custom calls
(device trace)."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"] or not t["by_kind_s"].get("pallas"):
        return None
    return 100.0 * t["by_kind_s"]["pallas"] / t["busy_s"]
