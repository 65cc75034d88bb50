"""Attention calls lowered in this process that took the dense
composition instead of a Pallas kernel family
(pt_attention_dispatch_total; it counts only with telemetry on, that
is in traced runs). 0 is expected in every train cell."""


def read(run):
    a = run.counters.get("after")
    if not a or not a["attention_dispatch"]:
        return None
    return sum(v for k, v in a["attention_dispatch"].items()
               if k.startswith("dense "))
