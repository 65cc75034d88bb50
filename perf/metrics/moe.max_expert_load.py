"""Rows of the fullest expert over the mean, the largest over the
blocks: what the program's ``expert_rows`` fetch read on the
correctness sample (the family's second check keeps it). 1.0 is an even
load; the grouped matmuls take every row whatever the load, so an
uneven one shows as time, never as dropped tokens."""


def read(run):
    return (run.check.get("second") or {}).get("max_expert_load")
