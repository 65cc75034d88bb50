"""Of the bytes the train step's forward pass keeps for its backward
pass, the share that is tile padding, in % (``pt_program_memory_bytes``
kind ``saved_padding`` over kind ``saved``): a ``[b, h, t, 1]`` float32
column is 128-fold padded, a ``[b, tq, 8]`` one sixteen-fold. The rows
padded twofold or more go to the run's log (perf/mem_ledger.py)."""

from perf import mem_ledger


def read(run):
    led = mem_ledger.train_ledger(run)
    if led is None or not led["saved"]["padded_bytes"]:
        return None
    saved = led["saved"]
    return 100.0 * (saved["padded_bytes"] - saved["bytes"]) \
        / saved["padded_bytes"]
