"""Share of the device's busy self time in the rotary embedding,
whichever way it lowered: the self time of every op under a
``rotary_embedding`` / ``rotary_embedding_grad`` op or under a name
scope ``rope`` (perf/spans.py, the table by scope). The ``rope.fwd`` /
``rope.bwd`` kernels' calls carry their op's scope
(``*/blk<i>/attn[/rope]/rotary_embedding[_grad]/...``), so the family
``rope`` is in the sum where a kernel took the call, with XLA's copies
around it, and where none did, XLA's ``_rotate`` and its transposes
are; under a ``rope`` scope a builder may also keep the splits and the
assembly of a partly rotated head (``models/joyai_flash.py``). None
where the trace holds neither such an op nor such a scope."""

from perf import spans

OPS = ("rotary_embedding", "rotary_embedding_grad")


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    ns = spans.scope_ns(
        s, lambda parts: parts[-1] in OPS or "rope" in parts[1:-1])
    return 100.0 * ns / s["busy_ns"] if ns else None
