"""Tokens emitted over (decode steps x slots) in the window: the share
of each decode step's rows that carried a request
(ServingEngine.stats() deltas)."""


def read(run):
    w = run.window
    if not w.get("decode_steps"):
        return None
    return w["tokens_emitted"] / (w["decode_steps"] * w["slots"])
