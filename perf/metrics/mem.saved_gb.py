"""GB of the values the train step's forward pass keeps for its
backward pass (written under ``fwd``, or fed, and last read by a ``bwd``
or ``opt`` op), padded to the chip's tiles (``pt_program_memory_bytes``
kind ``saved``; the ten largest rows go to the run's log:
perf/mem_ledger.py). The Program's variables: what XLA fuses away or
makes again is counted all the same."""

from perf import mem_ledger


def read(run):
    return mem_ledger.gb(run, lambda led: led["saved"]["padded_bytes"])
