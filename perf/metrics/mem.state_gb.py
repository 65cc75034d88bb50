"""GB (1e9 bytes, as ``device.peak_hbm_gb.train``) of the train step's
state arrays as the lowering traced them: parameters and optimizer
state (``pt_program_memory_bytes`` kinds ``param`` + ``optimizer``; the
record is ``monitor.memory_ledgers()``: perf/mem_ledger.py)."""

from perf import mem_ledger


def read(run):
    return mem_ledger.gb(
        run, lambda led: led["state"]["param"] + led["state"]["optimizer"])
