"""Device mesh management.

Replaces the reference's device-topology plumbing (places lists, NCCL
context maps, `nccl_comm_num` rings, hierarchical inter/exter comms —
reference: platform/nccl_helper.h:90-210, parallel_executor.cc:343-366)
with one object: a named `jax.sharding.Mesh`. Multi-host comes from
jax.distributed + the same mesh spanning all processes; ICI vs DCN layout
is expressed by axis order (outer axes ride DCN across slices).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_current_mesh: Optional[Mesh] = None


def create_mesh(
    axes: Dict[str, int],
    devices: Optional[Sequence] = None,
    set_as_default: bool = True,
) -> Mesh:
    """Create a named mesh, e.g. create_mesh({"data": 4, "model": 2}).

    Axis sizes must multiply to the device count; -1 on one axis infers it.
    """
    devs = list(devices) if devices is not None else jax.devices()
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devs) // known
    total = int(np.prod(sizes))
    if total != len(devs):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devs)}"
        )
    arr = np.asarray(devs).reshape(sizes)
    mesh = Mesh(arr, tuple(names))
    if set_as_default:
        set_mesh(mesh)
    return mesh


def axis_tuple(axis) -> tuple:
    """Normalize an axis spec (None | str | tuple of str) to a tuple.
    Composed batch axes — the multi-slice (slice, data) pair — travel
    through SpmdCtx as tuples; single axes stay strings."""
    if axis is None:
        return ()
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def axis_size(mesh: Mesh, axis) -> int:
    """Total ranks across one axis or a composed tuple of axes."""
    n = 1
    for a in axis_tuple(axis):
        n *= int(mesh.shape[a])
    return n


def create_slice_mesh(
    n_slices: int,
    within_axes: Dict[str, int],
    slice_axis: str = "slice",
    devices: Optional[Sequence] = None,
    set_as_default: bool = True,
) -> Mesh:
    """Mesh with an OUTER cross-slice axis riding DCN and inner axes
    riding ICI — the topology behind the reference's 2-level
    hierarchical allreduce (reference: platform/nccl_helper.h:179-210).

    On real multi-slice hardware the devices are ordered so each slice's
    chips are contiguous (``jax.devices()`` groups by slice; for
    irregular topologies use jax.experimental.mesh_utils'
    ``create_hybrid_device_mesh`` and wrap the result in ``Mesh``
    yourself). GSPMD then lowers a gradient all-reduce over
    ``(slice, data)`` into within-slice reduce-scatter (ICI) +
    cross-slice all-reduce (DCN) + within-slice all-gather
    automatically — no hand-placed collectives.
    """
    devs = list(devices) if devices is not None else jax.devices()
    per_slice = int(np.prod(list(within_axes.values())))
    if n_slices * per_slice != len(devs):
        raise ValueError(
            f"slice mesh ({n_slices} x {within_axes}) needs "
            f"{n_slices * per_slice} devices, have {len(devs)}"
        )
    axes = {slice_axis: n_slices, **within_axes}
    return create_mesh(axes, devices=devs, set_as_default=set_as_default)


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """{axis name -> size} — the static verifier embeds this in every
    collective-signature entry (analysis.collective_signature) so two
    ranks that built DIFFERENT meshes diff as a participant-set
    divergence; per-axis participant counts / reshard-cost denominators
    use ``axis_size`` (singular, composed-axis aware) above."""
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def sharding_descriptor(sharding) -> Optional[dict]:
    """JSON-able description of a NamedSharding — the manifest-v2 field
    that makes checkpoints mesh-portable: ``{"mesh": {axis -> size},
    "spec": [per-dim axis list | None, ...]}``. Device identity is
    deliberately NOT recorded (it is exactly what a restore onto a
    different topology must ignore); axis names + sizes + the partition
    spec are the whole layout. Non-Named shardings (positional/GSPMD) and
    host values return None — their checkpoints still restore, they just
    cannot advertise a layout to rebuild."""
    if not isinstance(sharding, NamedSharding):
        return None
    spec = []
    for e in tuple(PartitionSpec(*sharding.spec)):
        if e is None:
            spec.append(None)
        elif isinstance(e, (tuple, list)):
            spec.append([str(a) for a in e])
        else:
            spec.append([str(e)])
    return {"mesh": axis_sizes(sharding.mesh), "spec": spec}


def sharding_from_descriptor(desc: dict, devices=None):
    """Rebuild a NamedSharding from a manifest-v2 descriptor over THIS
    process's devices (or ``devices``). The reconstructed mesh shares
    only axis names/sizes with the saving one — which is all a layout
    is; use it to restore a checkpoint in its original sharding when the
    restoring program has no strategy of its own."""
    mesh = create_mesh(dict(desc["mesh"]), devices=devices,
                       set_as_default=False)
    entries = []
    for e in desc["spec"]:
        if e is None:
            entries.append(None)
        elif len(e) == 1:
            entries.append(e[0])
        else:
            entries.append(tuple(e))
    return NamedSharding(mesh, PartitionSpec(*entries))


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Multi-host bootstrap (replaces gen_nccl_id RPC bootstrap, reference:
    operators/distributed_ops/gen_nccl_id_op.cc:62): the PJRT distributed
    runtime's KV store handles device discovery and barriers."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)
