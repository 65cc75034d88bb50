"""1 minus the union of device-op intervals over the traced window
(device trace, averaged over the chips)."""


def read(run):
    t = run.trace
    if not t or t["idle_share"] is None:
        return None
    return 100.0 * t["idle_share"]
