"""Share of the device's busy self time in REPLAYED forward ops: what
backward.append_backward appended again between two checkpoints so that
the backward pass keeps a layer's input and not its activations
(``recompute_spans.replayed``: phase ``bwd``, a name scope, a forward
op's type). The price of the memory ``mem.saved_gb.train`` no longer
holds; a later PR that keeps more or replays less lowers it."""

from perf import recompute_spans, spans


def read(run):
    ns = recompute_spans.replay_ns(run)
    return ns and 100.0 * ns / spans.for_run(run)["busy_ns"]
