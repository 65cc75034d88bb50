"""From a configuration file to the program's own model, through
paddle_tpu's public functions only (Program, optimizer, Executor, Scope
and the model zoo). What belongs to ONE model family (how its config
object, graph, feeds and FLOPs are made) sits in
perf/families/<family>.py, found by the ``family`` key of the
configuration file; a later family is a new file there."""

from __future__ import annotations

import importlib
from typing import Dict


def family(cfg: Dict):
    """perf/families/<family>.py of a configuration file."""
    return importlib.import_module(f"perf.families.{cfg['family']}")


def reference(cfg: Dict):
    """perf/reference/<family>.py: the family's plain reference."""
    return importlib.import_module(f"perf.reference.{cfg['family']}")


def kind(name: str):
    """perf/kinds/<kind>.py: the loop of a kind of cell."""
    return importlib.import_module(f"perf.kinds.{name}")


def program_seed(seed: int) -> int:
    """--seed may pass 2**31; a program's random_seed is a signed int."""
    return int(seed) % (2 ** 31 - 1)


def build_train(cfg: Dict, seed: int, lr: float = 1e-4):
    """(main, startup, eval clone, loss variable, the dict the family's
    ``build_graph`` returned) of the family's training graph under bf16
    AMP with Adam. The eval clone is the same graph with dropout off
    and no optimizer: the correctness sample runs it, and fetches from
    it by the dict's variables. Weights are drawn from ``seed`` by the
    startup program, on the device."""
    import paddle_tpu as fluid

    fam = family(cfg)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = program_seed(seed)
    with fluid.program_guard(main, startup):
        model = fam.build_graph(fam.program_config(cfg))
        evalp = main.clone(for_test=True)
        fluid.optimizer.Adam(lr).minimize(model["loss"])
    main._amp = True   # bf16 matmuls, f32 master weights
    evalp._amp = True  # the sample runs at the precision that is trained
    return main, startup, evalp, model["loss"], model


def build_serve_weights(cfg: Dict, seed: int):
    """(program config, scope holding the weights) for the serving
    engine: the inference graph's startup program run once, on the
    device, from ``seed``."""
    import paddle_tpu as fluid

    fam = family(cfg)
    pcfg = fam.program_config(cfg, **fam.SERVE_OVERRIDES)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = program_seed(seed)
    scope = fluid.Scope()
    with fluid.program_guard(main, startup):
        fam.build_graph(pcfg, is_test=True)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    exe.close()
    return pcfg, scope
