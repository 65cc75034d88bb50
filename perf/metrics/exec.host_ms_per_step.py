"""Host milliseconds per executor call outside the device wait: the
feed + dispatch + fetch phases of pt_step_phase_seconds over the phase
stretch of a traced run (harness.PhaseProbe: every call of it is
timed; the executor's "device" phase is the host's wait, not device
time, and is left out)."""


def read(run):
    b = run.counters.get("phases_before")
    a = run.counters.get("phases_after")
    if not (b and a):
        return None
    n = a["phase_count"] - b["phase_count"]
    if n <= 0:
        return None
    host = sum(a["phase_sum_s"][p] - b["phase_sum_s"][p]
               for p in ("feed", "dispatch", "fetch"))
    return host / n * 1e3
