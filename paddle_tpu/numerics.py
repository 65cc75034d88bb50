"""Device-side numerics observability plane.

The third observability plane (after monitor.py's host telemetry and the
compile-cost reports): *what the numbers are doing on the device*. The
reference could only offer a post-hoc host scan (``FLAGS_check_nan_inf``,
operator.cc:950) that says "something went non-finite"; instrumented-graph
numerics debugging (tfdbg, Cai et al. 2016) is the proven shape for
define-then-run frameworks, and on TPU the stats must be computed
*in-graph* — dragging every tensor to host would serialize the step.

Three pieces:

1. **``numerics_stats`` op** — one registered kernel that reduces every
   instrumented var to a tiny stats vector (non-finite count, max-abs,
   rms, optional log2-magnitude histogram) and concatenates all of them
   plus any registered aux scalars (AMP loss scale, grad global norm)
   into ONE 1-D f32 bundle. The reductions fuse into the step's XLA
   program; the bundle is a single auxiliary fetch — one device->host
   transfer per sampled step, no extra host syncs.

2. **``instrument(program)``** (exposed as the ``instrument_numerics``
   pass in passes.py) — selects op outputs (activations, gradients,
   parameters; filtered by the ``numerics_vars`` flag) and appends the
   stats op, attaching a ``NumericsPlan`` to the program that maps each
   bundle slot back to (var, producing op index, op type).

3. **``decode(...)``** — called by the executor after a sampled step:
   one ``np.asarray`` of the bundle, then pure host bookkeeping into the
   monitor registry (``pt_tensor_maxabs{var=}``, ``pt_tensor_rms{var=}``,
   ``pt_nonfinite_total{op=,var=}``, AMP/clip instruments) plus a
   **provenance record** naming the first op (index, type, output var)
   that produced a non-finite value — browsable via
   ``provenance_records()``, the monitor server's ``/numerics`` route,
   and ``debugger.pprint_program`` annotations.

Everything is off by default: decoding is gated on the ``telemetry`` AND
``numerics`` flags (``active()`` is one module-level boolean read, the
same zero-allocation contract the monitor instruments honor), and the
``numerics_every_n_steps`` flag bounds enabled-mode overhead.
"""

from __future__ import annotations

import collections
import dataclasses
import fnmatch
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from paddle_tpu import flags as _flags
from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op

# ---------------------------------------------------------------------------
# instruments (registered eagerly so a first /metrics scrape and the
# doc-coverage test see the full set)
# ---------------------------------------------------------------------------

_M_MAXABS = _monitor.gauge(
    "pt_tensor_maxabs",
    "max |finite value| of an instrumented tensor at the last sampled "
    "step, by var")
_M_RMS = _monitor.gauge(
    "pt_tensor_rms",
    "rms of finite values of an instrumented tensor at the last sampled "
    "step, by var")
_M_NONFINITE = _monitor.counter(
    "pt_nonfinite_total",
    "non-finite elements observed in instrumented tensors at sampled "
    "steps, by producing op type and var")
_M_DECODES = _monitor.counter(
    "pt_numerics_decodes_total",
    "numerics bundles decoded (one auxiliary transfer each)")
_M_AMP_SCALE = _monitor.gauge(
    "pt_amp_loss_scale", "current AMP dynamic loss scale")
_M_AMP_SKIPS = _monitor.counter(
    "pt_amp_overflow_skips_total",
    "AMP steps whose parameter update was skipped on overflow")
_M_GRAD_NORM = _monitor.gauge(
    "pt_grad_global_norm",
    "pre-clip global gradient norm at the last sampled step")
_M_CLIP_RATIO = _monitor.gauge(
    "pt_grad_clip_ratio",
    "global-norm clip scale at the last sampled step (1.0 = no clip)")
_M_CLIPS = _monitor.counter(
    "pt_grad_clips_total",
    "sampled steps where global-norm clipping actually triggered")

# ---------------------------------------------------------------------------
# enable/disable plumbing (cached hot flags; see flags.watch_flag)
# ---------------------------------------------------------------------------

_active = False
_every_n = 1


def active() -> bool:
    """Whether executors should fetch + decode numerics bundles: the
    ``telemetry`` AND ``numerics`` flags (one boolean read)."""
    return _active


def _sync_active(_value=None):
    global _active
    _active = bool(_flags.get_flag("telemetry")) and bool(
        _flags.get_flag("numerics"))


def _sync_every_n(value):
    global _every_n
    _every_n = max(1, int(value))


def should_sample(step: int, steps: int = 1) -> bool:
    """Whether a call's bundle gets decoded (the
    ``numerics_every_n_steps`` sampling gate): a compiled window samples
    once when ANY of its steps lands on the period (its single bundle
    stands in for all of them)."""
    return (step + steps - 1) // _every_n > (step - 1) // _every_n


# ---------------------------------------------------------------------------
# the in-graph stats kernel
# ---------------------------------------------------------------------------

STAT_FIELDS = ("nonfinite", "maxabs", "rms")
# log2-magnitude histogram range: 2^-16 .. 2^16 covers bf16/f32 training
# streams; values outside clamp into the edge bins
HIST_LO, HIST_HI = -16.0, 16.0


def _stats_vec(x, bins: int):
    xf = x.astype(jnp.float32)
    finite = jnp.isfinite(xf)
    n_finite = jnp.sum(finite, dtype=jnp.int32)
    n_bad = (xf.size - n_finite).astype(jnp.float32)
    safe = jnp.where(finite, xf, 0.0)
    maxabs = jnp.max(jnp.abs(safe))
    # rms over the FINITE values only: dividing the zero-filled sum by
    # the full size would understate it exactly when tensors go bad
    rms = jnp.sqrt(jnp.sum(jnp.square(safe))
                   / jnp.maximum(n_finite, 1).astype(jnp.float32))
    head = jnp.stack([n_bad, maxabs, rms])
    if not bins:
        return head
    mag = jnp.abs(safe)
    nz = (finite & (mag > 0)).reshape(-1)
    l2 = jnp.log2(jnp.where(nz, mag.reshape(-1), 1.0))
    frac = (jnp.clip(l2, HIST_LO, HIST_HI) - HIST_LO) / (HIST_HI - HIST_LO)
    idx = jnp.clip((frac * bins).astype(jnp.int32), 0, bins - 1)
    hist = jnp.zeros((bins,), jnp.float32).at[idx].add(
        nz.astype(jnp.float32))
    return jnp.concatenate([head, hist])


@register_op("numerics_stats", no_grad=True,
             doc="reduce instrumented vars to one stats bundle "
                 "(numerics.py device-side observability)")
def _numerics_stats(ins, attrs):
    bins = int(attrs.get("hist_bins", 0))
    parts = [_stats_vec(x, bins) for x in ins.get("X", [])]
    # aux scalars (loss scale, found-inf flag, grad norms) ride the same
    # bundle so the sampled step still costs exactly one transfer
    parts += [a.astype(jnp.float32).reshape(-1)[:1]
              for a in ins.get("A", [])]
    return {"Out": [jnp.concatenate(parts)]}


# ---------------------------------------------------------------------------
# instrumentation plan
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = frozenset(
    {"float16", "float32", "float64", "bfloat16"})

BUNDLE_VAR = "__numerics_bundle__"


@dataclasses.dataclass
class NumericsPlan:
    """Decode map for an instrumented program: bundle slot -> meaning."""

    program_uid: int
    # (var name, producing op index, op type, kind) per stats slot group
    entries: Tuple[Tuple[str, int, str, str], ...]
    # (aux kind, var name) per trailing scalar slot
    aux: Tuple[Tuple[str, str], ...]
    bundle_var: str = BUNDLE_VAR
    hist_bins: int = 0
    # True while the current non-finite episode has already been recorded
    # (provenance fires on the FIRST sampled decode that sees a bad var)
    _bad_episode: bool = False
    # last decoded value per CUMULATIVE aux kind (amp_overflow_skips):
    # the decoder emits deltas, so sampled/windowed decodes stay exact
    _aux_prev: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def stats_width(self) -> int:
        return len(STAT_FIELDS) + self.hist_bins

    @property
    def bundle_size(self) -> int:
        return len(self.entries) * self.stats_width + len(self.aux)


def register_aux(program, kind: str, var_name: str):
    """Register an in-graph scalar (AMP loss scale, grad global norm ...)
    for bundle pickup. Pure metadata — costs nothing until a plan is
    built and the numerics plane is active."""
    aux = program.__dict__.setdefault("_numerics_aux", [])
    if (kind, var_name) not in aux:
        aux.append((kind, var_name))


def _patterns() -> List[str]:
    raw = _flags.get_flag("numerics_vars")
    return [p.strip() for p in raw.split(",") if p.strip()]


def instrument(program, vars: Optional[Sequence[str]] = None,
               histogram_bins: int = 0,
               include: Sequence[str] = ("activation", "gradient",
                                         "parameter")) -> Optional[NumericsPlan]:
    """Append the ``numerics_stats`` op to ``program``'s global block and
    attach the decode plan. Apply AFTER the program is fully built
    (minimize/clip/AMP included) — later-appended ops are not seen.

    ``vars``: explicit var names to instrument (None = every float op
    output, filtered by the ``numerics_vars`` flag patterns; ``()`` =
    aux-only). Idempotent: an already-instrumented program returns its
    existing plan."""
    existing = getattr(program, "_numerics_plan", None)
    if existing is not None:
        return existing
    block = program.global_block()
    first_writer: Dict[str, Tuple[int, str]] = {}
    for idx, op in enumerate(block.ops):
        for n in op.output_arg_names:
            if n:
                first_writer.setdefault(n, (idx, op.type))

    entries: List[Tuple[str, int, str, str]] = []
    if vars is not None:
        wanted = list(vars)
        for name in wanted:
            if name not in first_writer:
                raise KeyError(
                    f"numerics: var '{name}' is not produced by any op "
                    f"in block 0")
            idx, op_type = first_writer[name]
            entries.append((name, idx, op_type, _kind_of(block, name)))
    else:
        pats = _patterns()
        for name, (idx, op_type) in first_writer.items():
            v = block._find_var_recursive(name)
            if v is None or v.dtype not in _FLOAT_DTYPES:
                continue
            kind = _kind_of(block, name)
            if kind not in include:
                continue
            if pats and not any(fnmatch.fnmatch(name, p) for p in pats):
                continue
            entries.append((name, idx, op_type, kind))
        entries.sort(key=lambda e: e[1])

    aux = tuple(getattr(program, "_numerics_aux", ()) or ())
    if not entries and not aux:
        return None
    plan = NumericsPlan(
        program_uid=int(program._uid),
        entries=tuple(entries),
        aux=aux,
        hist_bins=int(histogram_bins),
    )
    block.create_var(name=plan.bundle_var, dtype="float32",
                     shape=[plan.bundle_size], stop_gradient=True)
    block.append_op(
        "numerics_stats",
        inputs={"X": [e[0] for e in plan.entries],
                "A": [v for _, v in plan.aux]},
        outputs={"Out": [plan.bundle_var]},
        attrs={"hist_bins": plan.hist_bins},
    )
    program._numerics_plan = plan
    return plan


def _kind_of(block, name: str) -> str:
    if name.endswith("@GRAD"):
        return "gradient"
    v = block._find_var_recursive(name)
    if v is not None and v.persistable:
        return "parameter"
    return "activation"


def plan_for(program) -> Optional[NumericsPlan]:
    """The executor's entry point (called only while ``active()``): the
    attached plan, or a lazily built aux-only plan when graph code
    registered aux vars (AMP scale, clip norms) without the full pass."""
    plan = getattr(program, "_numerics_plan", None)
    if plan is None and getattr(program, "_numerics_aux", None):
        plan = instrument(program, vars=())
    return plan


# ---------------------------------------------------------------------------
# decoding
# ---------------------------------------------------------------------------

# Test hook AND the single device->host sync point: decode() calls this
# exactly once per sampled bundle.
_to_host = np.asarray

_LOCK = threading.Lock()
PROVENANCE_CAPACITY = 64
_PROVENANCE: collections.deque = collections.deque(
    maxlen=PROVENANCE_CAPACITY)
# program uid -> latest decoded summary (stats + aux), for /numerics
_LATEST: Dict[int, Dict[str, Any]] = {}

PROVENANCE_SCHEMA_VERSION = 1

# aux kind -> gauge/event handler for PER-STEP values. amp_found_inf is
# report-only here (it rides the step record); the skip COUNTER comes
# from the cumulative amp_overflow_skips kind below, which stays exact
# under sampling and compiled windows.
_AUX_DECODERS = {
    "amp_loss_scale": lambda v: _M_AMP_SCALE.set(v),
    "grad_global_norm": lambda v: _M_GRAD_NORM.set(v),
    "grad_clip_scale": lambda v: (
        _M_CLIP_RATIO.set(v),
        _M_CLIPS.inc() if v < 1.0 else None),
}

# aux kinds whose in-graph var is a monotonically increasing counter:
# the decoder emits value - last_decoded_value into the metric
_AUX_CUMULATIVE = {
    "amp_overflow_skips": _M_AMP_SKIPS,
}


def decode(program, plan: NumericsPlan, bundle, step: int,
           kind: str = "step",
           nan_step: Optional[int] = None) -> Dict[str, Any]:
    """Decode one fetched bundle (ONE ``np.asarray`` — the auxiliary
    transfer) into the monitor registry + provenance ring. Returns the
    compact summary embedded in the step record's ``numerics`` field.
    Never raises — telemetry must not fail a step."""
    try:
        return _decode(program, plan, bundle, step, kind, nan_step)
    except Exception as e:
        import warnings

        warnings.warn(f"numerics decode dropped: {e!r}", RuntimeWarning)
        return {"error": str(e)}


def _decode(program, plan, bundle, step, kind, nan_step):
    arr = np.asarray(_to_host(bundle), dtype=np.float64).reshape(-1)
    _M_DECODES.inc()
    w = plan.stats_width
    stats: Dict[str, Dict[str, float]] = {}
    bad: List[Tuple[str, int, str, Dict[str, float]]] = []
    for i, (var, op_idx, op_type, var_kind) in enumerate(plan.entries):
        off = i * w
        cell = {
            "nonfinite": float(arr[off]),
            "maxabs": float(arr[off + 1]),
            "rms": float(arr[off + 2]),
            "kind": var_kind,
            "op": op_idx,
            "op_type": op_type,
        }
        if plan.hist_bins:
            cell["hist"] = [float(c)
                            for c in arr[off + 3:off + 3 + plan.hist_bins]]
        stats[var] = cell
        _M_MAXABS.set(cell["maxabs"], labels={"var": var})
        _M_RMS.set(cell["rms"], labels={"var": var})
        if cell["nonfinite"] > 0:
            _M_NONFINITE.inc(cell["nonfinite"],
                             labels={"op": op_type, "var": var})
            bad.append((var, op_idx, op_type, cell))
    aux_vals: Dict[str, float] = {}
    base = len(plan.entries) * w
    for j, (aux_kind, _var) in enumerate(plan.aux):
        v = float(arr[base + j])
        aux_vals[aux_kind] = v
        counter_m = _AUX_CUMULATIVE.get(aux_kind)
        if counter_m is not None:
            delta = v - plan._aux_prev.get(aux_kind, 0.0)
            plan._aux_prev[aux_kind] = v
            if delta > 0:
                counter_m.inc(delta)
            continue
        dec = _AUX_DECODERS.get(aux_kind)
        if dec is not None:
            dec(v)

    summary: Dict[str, Any] = {
        "vars": len(plan.entries),
        "nonfinite_vars": len(bad),
        "first_bad": None,
    }
    if aux_vals:
        summary["aux"] = aux_vals
    if bad:
        var, op_idx, op_type, cell = min(bad, key=lambda b: b[1])
        first = {"op": op_idx, "op_type": op_type, "var": var}
        summary["first_bad"] = first
        if not plan._bad_episode:
            plan._bad_episode = True
            rec = {
                "v": PROVENANCE_SCHEMA_VERSION,
                "ts": time.time(),
                "step": int(step),
                "kind": kind,
                "program": f"program{plan.program_uid}",
                "program_uid": plan.program_uid,
                "op_idx": op_idx,
                "op_type": op_type,
                "var": var,
                "nonfinite": cell["nonfinite"],
                "maxabs": cell["maxabs"],
                "rms": cell["rms"],
                "nan_step": nan_step,
            }
            with _LOCK:
                _PROVENANCE.append(rec)
    else:
        plan._bad_episode = False
    with _LOCK:
        _LATEST[plan.program_uid] = {
            "step": int(step), "kind": kind, "stats": stats,
            "aux": aux_vals,
        }
    return summary


def note_nonfinite(op_type: str, var: str, count: float = 1.0, *,
                   program_uid: int = -1, step: int = -1,
                   kind: str = "step",
                   maxabs: float = float("nan"),
                   rms: float = float("nan")):
    """Host-side non-finite report from a plane that detects poison
    OUTSIDE the in-graph bundle (e.g. serving.py's per-slot decode
    probe): counts ``pt_nonfinite_total{op=,var=}`` and appends a
    provenance record so the episode shows on ``/numerics`` beside the
    instrumented-program ones. Gated on telemetry; never raises."""
    if not _monitor.enabled():
        return
    try:
        _M_NONFINITE.inc(float(count), labels={"op": op_type, "var": var})
        rec = {
            "v": PROVENANCE_SCHEMA_VERSION,
            "ts": time.time(),
            "step": int(step),
            "kind": kind,
            "program": f"program{program_uid}",
            "program_uid": int(program_uid),
            "op_idx": -1,  # host-side detection: no in-graph op index
            "op_type": op_type,
            "var": var,
            "nonfinite": float(count),
            "maxabs": float(maxabs),
            "rms": float(rms),
            "nan_step": None,
        }
        with _LOCK:
            _PROVENANCE.append(rec)
    except Exception as e:
        import warnings

        warnings.warn(f"nonfinite note dropped: {e!r}", RuntimeWarning)


# ---------------------------------------------------------------------------
# inspection surface (/numerics route, debugger annotations, tests)
# ---------------------------------------------------------------------------

def provenance_records() -> List[Dict[str, Any]]:
    """Buffered NaN/Inf provenance records, oldest first."""
    with _LOCK:
        return [dict(r) for r in _PROVENANCE]


def provenance_for(program_uid: int) -> Optional[Dict[str, Any]]:
    """Latest provenance record for one program (None when clean)."""
    with _LOCK:
        for r in reversed(_PROVENANCE):
            if r["program_uid"] == program_uid:
                return dict(r)
    return None


def latest_stats() -> Dict[int, Dict[str, Any]]:
    """Latest decoded summary per program uid."""
    with _LOCK:
        return {k: dict(v) for k, v in _LATEST.items()}


def summary() -> Dict[str, Any]:
    """The /numerics route payload."""
    return {
        "active": _active,
        "every_n_steps": _every_n,
        "provenance": provenance_records(),
        "programs": {str(k): v for k, v in latest_stats().items()},
    }


def reset():
    """Drop decoded state (test isolation; monitor.reset calls this)."""
    with _LOCK:
        _PROVENANCE.clear()
        _LATEST.clear()


_flags.watch_flag("telemetry", _sync_active)
_flags.watch_flag("numerics", _sync_active)
_flags.watch_flag("numerics_every_n_steps", _sync_every_n)
