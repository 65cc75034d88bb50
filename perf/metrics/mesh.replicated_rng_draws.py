"""Random-word draws lowered in this process that every rank of some
mesh axis repeats (pt_rng_draw_total rows with a replicated_over; the
counter counts only with telemetry on, that is in traced runs, and at
lowering time: once per compile, not per step). 0 is expected in every
train cell: on one chip there is no axis to repeat over, and on four
each chip draws the words of its own rows. A program without the
counter (before PR 25) reports nothing."""


def read(run):
    from paddle_tpu.ops import nn_ops

    counts = getattr(nn_ops, "rng_draw_counts", None)
    rows = counts() if counts else {}
    if not rows:
        return None
    return sum(v for k, v in rows.items() if " replicated_over=" in k)
