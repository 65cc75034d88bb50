"""Share of device busy time in the embedding gradient's kernel: the
Mosaic calls of family ``embed`` (``embed.grad``,
parallel/embed_grad.py; device trace, perf/trace.py ``by_family_s``).
XLA's sort of the ids and gather of the rows in front of the kernel are
not in it (the table by scope has them under ``bwd/embed/``). None
where the trace holds no such call: a table the kernel refuses goes
through XLA's scatter-add, which ``lower.xla_embed_grad_calls`` counts."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"] or not t["by_family_s"].get("embed"):
        return None
    return 100.0 * t["by_family_s"]["embed"] / t["busy_s"]
