"""python perf/tools/lower_cell.py <train cell> [--dump FILE] [--no-compile] [--set key=int ...]

perf/README.md's rehearsal step 3 as a command, on the sandbox, no chip:
builds a train cell's step as kinds/train.py does, lowers it for a
described TPU v5e and prints the state's bytes, the sha256 of the
lowered StableHLO, the dispatch rows the lowering counted (attention,
grouped matmuls, the delta rule) and, unless --no-compile, what XLA's
``memory_analysis()`` says of the compiled step (Mosaic refuses a bad
tile here; an over-full chip fails with the largest buffers listed).
``--dump`` writes the StableHLO text, for compare_step_modules.py.
``--set num_experts=16`` lays integers over the configuration file.
Only one process at a time may load libtpu here."""

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--dump")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"   # the dispatch picks the kernels

    from paddle_tpu import flags, monitor
    from paddle_tpu.core import lowering
    from paddle_tpu.executor import Executor
    from perf import harness, models

    cell = harness.load_json("perf", "workloads", f"{args.cell}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    assert cell["kind"] == "train" and cell["chips"] == 1, cell
    cfg.update((k, int(v)) for k, v in (kv.split("=") for kv in args.set))
    flags.set_flags({"telemetry": True})
    main_p, _, _, loss, _ = models.build_train(cfg, 7)
    feeds_np = models.family(cfg).feeds(
        cfg, dict(cell["traffic"], feeds=1), 7)[0]

    def aval(shape, dtype):
        dtype = {"int64": "int32", "float64": "float32"}.get(
            jnp.dtype(dtype).name, jnp.dtype(dtype).name)
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    feeds = {k: aval(v.shape, v.dtype) for k, v in feeds_np.items()}
    low = lowering.lower_block(main_p, 0, tuple(feeds), (loss.name,))
    block = main_p.global_block()
    state = {n: aval(block._find_var_recursive(n).shape,
                     block._find_var_recursive(n).dtype)
             for n in low.state_in_names}
    n_param = sum(int(np.prod(p.shape)) for p in main_p.all_parameters())
    n_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in state.values())
    print(f"parameters {n_param / 1e6:.2f}M, state {n_bytes / 1e9:.3f} GB")
    lowered = Executor._jit_for(low, None).lower(
        state, feeds, aval((2,), "uint32"), aval((), "uint32"))
    text = lowered.as_text()
    print("stablehlo sha256", hashlib.sha256(text.encode()).hexdigest(),
          len(text), "characters")
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    rows = {name: {" ".join(f"{k}={v}" for k, v in r["labels"].items()):
                   int(r["value"]) for r in c["values"]}
            for name, c in monitor.snapshot().items()
            if name.endswith("_dispatch_total") and c["values"]}
    print(json.dumps(rows, indent=1))
    if args.no_compile:
        return
    m = lowered.compile().memory_analysis()
    print(f"compiled for {topo.devices[0].device_kind}: peak "
          f"{m.peak_memory_in_bytes / 1e9:.3f} GB (arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f}, aliased with the outputs "
          f"{m.alias_size_in_bytes / 1e9:.3f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f}, code "
          f"{m.generated_code_size_in_bytes / 1e9:.3f})")


if __name__ == "__main__":
    main()
