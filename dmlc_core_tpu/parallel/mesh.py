"""Mesh + sharding helpers (the TPU-native "topology service").

The reference's rendezvous tracker computes a binary tree and a shared-node
ring over worker TCP sockets (tracker.py:185-252) for Rabit's allreduce.  On
TPU those topologies are obsolete: the ICI torus is physical, XLA chooses the
collective algorithm, and what remains of "topology" is *mesh shape* — how the
device grid is factored into named axes (data/model/...), and whether an axis
crosses slice boundaries (DCN) or stays inside a slice (ICI).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from dmlc_core_tpu.utils.logging import CHECK

__all__ = [
    "make_mesh",
    "make_hybrid_mesh",
    "data_sharding",
    "replicated_sharding",
    "local_shard_info",
]


def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None):
    """Build a Mesh from named axis sizes, e.g. ``{"data": 4, "model": 2}``.

    One axis may be -1 (inferred).  Default: 1-D ``data`` mesh over all
    devices.  On hardware ``mesh_utils.create_device_mesh`` assigns devices
    along the physical ICI topology, and a failure there is a real error (a
    silent reshape would lay named axes across the wrong links) and
    propagates.  CPU devices have no topology: they are reshaped in id
    order, which keeps axis semantics testable on a virtual mesh.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    ndev = len(devices)
    if not axes:
        axes = {"data": ndev}
    names = tuple(axes.keys())
    sizes = list(axes.values())
    n_infer = sum(1 for s in sizes if s == -1)
    CHECK(n_infer <= 1, "at most one mesh axis may be -1")
    if n_infer:
        known = int(np.prod([s for s in sizes if s != -1]))
        CHECK(ndev % known == 0, f"{ndev} devices not divisible by {known}")
        sizes = [ndev // known if s == -1 else s for s in sizes]
    CHECK(int(np.prod(sizes)) == ndev,
          f"mesh axes {dict(zip(names, sizes))} do not cover {ndev} devices")
    if all(d.platform == "cpu" for d in devices):
        dev_array = np.asarray(devices).reshape(sizes)
    else:
        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    return Mesh(dev_array, names)


def make_hybrid_mesh(ici_axes: Dict[str, int], dcn_axes: Dict[str, int]):
    """Multi-slice mesh: ``dcn_axes`` cross slices (DCN), ``ici_axes`` stay
    within a slice (ICI) — e.g. ``make_hybrid_mesh({"model": 8}, {"data": 4})``
    for 4 slices of 8 chips.  This is how the reference's multi-host scale-out
    (tracker launching N hosts) maps onto TPU pods."""
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    dcn_shape = tuple(dcn_axes.values()) + tuple(1 for _ in ici_axes)
    ici_shape = tuple(1 for _ in dcn_axes) + tuple(ici_axes.values())
    import jax

    # virtual/CPU devices carry no usable slice_index, so the topology-aware
    # builder cannot run there; a plain reshape (dcn axes outermost) keeps
    # the axis semantics so the hybrid layout stays testable off-hardware.
    # On real sliced hardware a builder failure is a REAL error (a silent
    # reshape would put ICI-named axes across DCN links) and propagates.
    sliced_hw = any(getattr(d, "slice_index", None) is not None
                    for d in jax.devices())
    if sliced_hw:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, allow_split_physical_axes=True)
    else:
        shape = tuple(d * i for d, i in zip(dcn_shape, ici_shape))
        ndev = int(np.prod(shape))
        devices = np.asarray(jax.devices()[:ndev])
        CHECK(len(devices) == ndev,
              f"hybrid mesh {dict(zip(names, shape))} needs {ndev} devices")
        dev_array = devices.reshape(shape)
    return Mesh(dev_array, names)


def data_sharding(mesh, axis: str = "data", ndim: int = 1):
    """NamedSharding placing dim 0 on ``axis``, rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(axis, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def local_shard_info() -> Tuple[int, int]:
    """(part_index, num_parts) for this process — the InputSplit shard this
    host should read (SURVEY.md §7 stage 4: per-host shard = process index)."""
    import jax

    return jax.process_index(), jax.process_count()
