"""Auto-derived gradient kernels.

The reference hand-writes a C++ grad kernel and a GradOpDescMaker per op
(reference: framework/grad_op_desc_maker.h; e.g. operators/mul_op.cc). Here a
``<type>_grad`` kernel is derived mechanically from the forward JAX kernel
with ``jax.vjp``: the grad op re-traces the forward inside the same XLA
computation and XLA CSEs the duplicated forward work. WHAT the backward
pass keeps of the forward pass is the Program's to say: between two
checkpoints a builder marked (``layers.checkpoint``),
``backward.append_backward`` appends the forward ops again behind a
``recompute_barrier`` and the grad ops' re-traces merge with that
replay, not with the first run; a Program without marks leaves it to
the compiler, which only makes values again once it is at its limit
(SURVEY.md section 7; PERF.md section 6, PRs 71 and 75).

Grad op desc convention (produced by backward.append_backward):
- inputs:  every forward input slot (same slot names), every forward output
  slot, plus ``GRAD::<out_slot>`` slots holding output gradients.
- outputs: ``GRAD::<in_slot>`` slots holding input gradients, aligned
  positionally with the forward input slot; "" marks a hole (no grad needed).
- attrs:   forward attrs + ``fwd_input_slots``/``fwd_output_slots`` +
  ``forward_op_idx`` (so stochastic ops replay the same PRNG key).
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpDef

GRAD_SLOT_PREFIX = "GRAD::"
_GRAD_META_ATTRS = ("fwd_input_slots", "fwd_output_slots", "forward_op_idx")


def _floatp(x) -> bool:
    try:
        return jnp.issubdtype(jnp.result_type(x), jnp.floating)
    except Exception:
        return False


def make_grad_compute(fwd: OpDef):
    """Build the compute fn for the auto grad op of ``fwd``."""

    def grad_compute(ins: Dict[str, List[Any]], attrs: Dict[str, Any], rng=None):
        in_slots = list(attrs["fwd_input_slots"])
        out_slots = list(attrs["fwd_output_slots"])
        fwd_attrs = {k: v for k, v in attrs.items() if k not in _GRAD_META_ATTRS}
        rng_kwargs = {"rng": rng} if fwd.needs_rng else {}

        fwd_ins = {s: list(ins.get(s, [])) for s in in_slots}

        # Which (slot, position) entries are differentiable.
        diff_keys: List[tuple] = []
        for s in in_slots:
            if fwd.diff_inputs is not None and s not in fwd.diff_inputs:
                continue
            for i, x in enumerate(fwd_ins[s]):
                if x is not None and _floatp(x):
                    diff_keys.append((s, i))

        # vjp over a pytree-valued forward: slot arity falls out of the
        # returned structure, so the forward is traced exactly once here
        # (the round-1 arity "probe" doubled trace size and compile time).
        def fwd_fn(diff_vals):
            merged = {s: list(v) for s, v in fwd_ins.items()}
            for (s, i), v in zip(diff_keys, diff_vals):
                merged[s][i] = v
            outs = fwd.compute(merged, fwd_attrs, **rng_kwargs)
            return {o: [y for y in outs.get(o, [])] for o in out_slots}

        primals = [fwd_ins[s][i] for (s, i) in diff_keys]
        out_tree, vjp_fn = jax.vjp(fwd_fn, primals)

        # Cotangents mirroring out_tree; zeros where the program did not
        # provide a gradient for an output.
        cotangents = {}
        for o in out_slots:
            gslot = ins.get(GRAD_SLOT_PREFIX + o, [])
            cots = []
            for i, y in enumerate(out_tree[o]):
                if y is None:
                    cots.append(None)
                    continue
                g = gslot[i] if i < len(gslot) else None
                if g is None:
                    g = jnp.zeros(jnp.shape(y), jnp.result_type(y))
                else:
                    g = jnp.asarray(g, jnp.result_type(y))
                    if jnp.shape(g) != jnp.shape(y):
                        g = jnp.broadcast_to(g, jnp.shape(y))
                cots.append(g)
            cotangents[o] = cots

        (grads,) = vjp_fn(cotangents)

        outs: Dict[str, List[Any]] = {}
        for (s, i), g in zip(diff_keys, grads):
            lst = outs.setdefault(GRAD_SLOT_PREFIX + s, [None] * len(fwd_ins[s]))
            lst[i] = g
        return outs

    grad_compute.__name__ = f"{fwd.type}_grad_compute"
    return grad_compute
