"""What the recomputation metrics share: which scopes of a traced run
are a REPLAY (forward ops that backward.append_backward appended again
under role ``bwd`` so that a segment's grad ops read them and not the
first run's values) and the rows of the program's
``pt_backward_recompute_ops_total`` counter. A program without marks (any
tree before recomputation existed, every cell whose builder marks
nothing) has no such scope and no such row: every function here then
returns None or nothing, and raises nothing."""

from perf import harness, spans

COUNTER = "pt_backward_recompute_ops_total"


def replayed(parts):
    """The ONE predicate over a ``by_scope_ns`` key's components (phase
    first, op type last): a replayed op keeps its first run's name scope
    (``bwd/blk3/mamba2/proj/mul``: the ``mamba2.*``, ``attn.*`` and
    ``step.block_share.train`` readers count it where its cost belongs)
    and is the backward phase's only op under a name scope whose type is
    a FORWARD op's: every grad op's type ends in ``_grad``, and the sums,
    fills and barriers between them carry no name scope."""
    return (parts[0] == "bwd" and len(parts) > 2
            and not parts[-1].endswith("_grad"))


def replay_ns(run):
    """Busy self time of the replayed ops, ns summed over the chips;
    None where the trace names no such op."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    return spans.scope_ns(s, replayed) or None


def replayed_ops():
    """Forward ops appended again, over the programs this process
    built with telemetry on; None where nothing was replayed."""
    rows = harness.counter_rows(COUNTER)
    return sum(n for _, n in rows) if rows else None
