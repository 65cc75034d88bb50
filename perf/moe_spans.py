"""What the MoE metrics share: the time of a mixture-of-experts block
in a traced run, from the program's scopes (perf/spans.py) and the
device trace's Mosaic calls (perf/trace.py).

The experts' grouped matmuls are Mosaic calls. Where the program names
a kernel of its own (``moe.<what>.<pass>``, family ``moe``) the call
sits under its op's scope like an attention kernel. Where they are
``jax.lax.ragged_dot``, libtpu expands each into a ``ragged-dot-none``
call whose op_name is just that: it carries no scope of the program,
so its time is in no ``blk*/moe`` row of the table by scope and is
added here to every sum that should hold it."""

from perf import spans

XLA_GMM = "ragged-dot-none"


def gmm_family(run):
    """The family of the Mosaic calls that do the grouped matmuls
    (``by_family_s``): the program's own, else XLA's; None without."""
    fams = (run.trace or {}).get("by_family_s") or {}
    return next((f for f in ("moe", XLA_GMM) if fams.get(f)), None)


def gmm_s(run):
    """Self seconds of those calls, averaged over the chips."""
    fam = gmm_family(run)
    return run.trace["by_family_s"][fam] if fam else 0.0


def unscoped_gmm_ns(run):
    """Their time where no scope holds it, in ``spans``' unit (ns
    summed over the chips)."""
    if gmm_family(run) != XLA_GMM:
        return 0.0
    return gmm_s(run) * 1e9 * run.trace["devices"]


def under(*path):
    """A ``spans.scope_ns`` predicate: the scope's components after the
    phase start with a ``blk<i>`` and then ``path``."""
    def accept(parts):
        inner = parts[1:-1]
        return (bool(inner) and inner[0].startswith("blk")
                and tuple(inner[1:1 + len(path)]) == path)
    return accept


def block_ns(run, s):
    """Busy self time of the decoder blocks (``*/blk*/``), ns."""
    return spans.scope_ns(s, under()) + unscoped_gmm_ns(run)


def moe_ns(run, s):
    """... of their expert layers (``*/blk*/moe/``)."""
    return spans.scope_ns(s, under("moe")) + unscoped_gmm_ns(run)


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/moe`` scope, else
    None: a program without such a block has nothing to report."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not spans.scope_ns(s, under("moe")):
        return None
    return s
