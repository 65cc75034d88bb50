"""Grouped matmuls of the expert layers lowered in this process that ran
as ``jax.lax.ragged_dot`` (libtpu's ``ragged-dot-none``) and not as the
program's ``moe.*`` kernels: pt_moe_gmm_dispatch_total rows with an
empty ``tile`` (the counter counts only with telemetry on, that is in
traced runs). 0 is expected on the chip: this is what guards an expert
width off the 128 lanes (1856), which ``gmm_tile`` once refused. None
where the program lowered no grouped matmul."""

from perf import mamba2_spans


def read(run):
    rows = mamba2_spans.gmm_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if not lb.get("tile"))
