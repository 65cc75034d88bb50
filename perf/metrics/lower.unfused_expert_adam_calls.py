"""Weight-gradient calls of the expert layers lowered in this process
whose gradient went to HBM as an array for an update that reads it back:
pt_moe_gmm_dispatch_total rows with ``pass`` ``bwd_dw`` and a tile
(the counter counts only with telemetry on, that is in traced runs).
Where the matrix's Adam step is taken inside the kernel the row's pass
is ``bwd_dw_adam`` instead. A tree before that form counts every matrix
of every lowering (three a gated layer, two a plain one); with it 0 is
expected on the chip wherever ``adam_tile`` has a tile for the matrix:
this is what shows a silent fall back to the two passes. None where the
program lowered no grouped matmul."""

from perf import mamba2_spans


def read(run):
    rows = mamba2_spans.gmm_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows
               if lb.get("pass") == "bwd_dw" and lb.get("tile"))
