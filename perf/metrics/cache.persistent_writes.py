"""Executables this process compiled and stored because jax's
persistent cache did not hold them (``pt_compile_cache_total`` rows
with ``outcome="written"``, every program, ``(outside)`` too): 0 on a
warm machine; a line on which it is not 0 reads its ``setup_s`` as a
cold one. The hits go to the run's log."""

from perf import setup_stages
from perf.harness import say


def read(run):
    name = "pt_compile_cache_total"
    written = setup_stages.total(
        run, name, "value", lambda lb: lb.get("outcome") == "written")
    if written is not None:
        hits = setup_stages.total(
            run, name, "value", lambda lb: lb.get("outcome") == "hit")
        say(f"perf: set-up: jax's persistent cache: {int(hits)} read, "
            f"{int(written)} compiled and written")
    return written
