"""GB at the peak of a liveness walk over the train step's ops: state
and feeds throughout, every other value from its write to its last
read (one nothing reads never), padded to the chip's tiles (``pt_program_memory_bytes`` kind
``walk_peak``; where the peak falls and what is alive there go to the
run's log: perf/mem_ledger.py). What the step would hold if XLA kept
every variable of the Program and nothing else: its distance to the
compiled peak is what fusion and XLA's temporaries are worth."""

from perf import mem_ledger


def read(run):
    return mem_ledger.gb(run, lambda led: led["walk_peak"]["bytes"])
