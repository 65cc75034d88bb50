"""Real (non-pad) tokens trained per second, summed over the cell's
chips: all the window's completed steps over all its time, the clock
stopped at block_until_ready on the last step's state."""


def read(run):
    w = run.window
    return w["tokens"] / w["seconds"] if w.get("steps") else None
