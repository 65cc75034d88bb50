"""jaxpr traces inside the first calls of the program's executables,
nested ones included (``pt_jax_traces_total`` less its ``(outside)``
row); the ten most traced function names go to the run's log."""

from perf import setup_stages


def read(run):
    name = "pt_jax_traces_total"
    n = setup_stages.total(
        run, name, "value",
        lambda lb: lb.get("fun_name") != setup_stages.OUTSIDE)
    if n is not None:
        setup_stages.say_top(run, name, "fun_name",
                             "most traced functions [name, traces]")
    return n
