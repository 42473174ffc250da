"""Device mesh construction.

TPU-native replacement for the reference's device topology handling
(NCCLContextMap per-device comms, /root/reference/paddle/fluid/platform/
nccl_helper.h:92; hierarchical inter/intra rings nccl_helper.h:185). On TPU
the topology is a named :class:`jax.sharding.Mesh`; collectives ride ICI
along mesh axes and DCN across slices — XLA picks the rings. Standard axis
names: ``dp`` (data), ``mp`` (tensor/model), ``pp`` (pipeline), ``sp``
(sequence/context), ``ep`` (expert).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DP, MP, PP, SP, EP = "dp", "mp", "pp", "sp", "ep"


def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None,
                allow_submesh: bool = False) -> Mesh:
    """Build a mesh from an axis→size dict, e.g. {"dp": 4, "mp": 2}.

    Sizes of -1 (at most one) absorb the remaining devices. Axis sizes that
    cover fewer devices than available are an error unless
    ``allow_submesh=True`` (which builds the mesh on the first ``total``
    devices and leaves the rest idle).
    """
    devices = list(devices) if devices is not None else jax.devices()
    axes = dict(axes) if axes else {DP: len(devices)}
    n = len(devices)
    known = 1
    wild = None
    for name, size in axes.items():
        if size == -1:
            wild = name
        else:
            known *= size
    if wild is not None:
        if known <= 0 or n % known != 0:
            raise ValueError(
                f"mesh axes {axes} with wildcard: {n} devices not "
                f"divisible by {known}")
        axes[wild] = n // known
    total = int(np.prod(list(axes.values())))
    if total > n or total <= 0:
        raise ValueError(f"mesh axes {axes} need {total} devices, have {n}")
    if total < n and not allow_submesh:
        raise ValueError(
            f"mesh axes {axes} cover {total} of {n} devices; use -1 to "
            f"absorb the rest or allow_submesh=True to idle them")
    arr = np.array(devices[:total]).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes))


def auto_axis_sizes() -> Dict[str, int]:
    """``{axis: size}`` of the axes larger than 1 that GSPMD partitions
    (``AxisType.Auto``) in the mesh in scope — the one
    ``jax.sharding.set_mesh`` holds while a sharded step traces; empty
    when there is none. What a trace may adapt to: no flag says it."""
    from jax.sharding import AxisType
    mesh = jax.sharding.get_abstract_mesh()
    return {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t == AxisType.Auto and s > 1}


def num_slices(devices: Optional[Sequence] = None) -> int:
    """Number of distinct TPU slices among ``devices`` (1 on CPU/GPU or a
    single slice). Multi-slice topologies expose ``slice_index`` on each
    device; collectives between different slice_index values ride DCN."""
    devices = list(devices) if devices is not None else jax.devices()
    idx = {getattr(d, "slice_index", 0) for d in devices}
    return len(idx)


def create_multislice_mesh(dcn_axes: Dict[str, int],
                           ici_axes: Dict[str, int],
                           devices: Optional[Sequence] = None) -> Mesh:
    """Slice-aware mesh: ``dcn_axes`` (outermost) cross slice boundaries
    and ride DCN; ``ici_axes`` stay within a slice and ride ICI.

    TPU-native equivalent of the reference's hierarchical allreduce
    (/root/reference/paddle/fluid/platform/nccl_helper.h:185
    NCCLCommunicator inter/exter rings;
    framework/distributed_strategy.proto:110 use_hierarchical_allreduce).
    Where the reference builds explicit two-level NCCL rings, here the
    mesh layout makes XLA emit the two-level reduction: sharding a batch
    over ``P(("dcn", "dp"))`` produces an intra-slice (ICI) reduce
    followed by an inter-slice (DCN) allreduce of the partial sums.

    On real multi-slice hardware the device→coordinate assignment comes
    from ``mesh_utils.create_hybrid_device_mesh`` (slice_index-aware); on
    a single slice or the virtual CPU backend, devices are grouped into
    ``prod(dcn_axes)`` contiguous synthetic slices so the same program
    (and tests) run anywhere. One ici axis may be -1 to absorb the
    remaining per-slice devices.
    """
    devices = list(devices) if devices is not None else jax.devices()
    dcn_axes = dict(dcn_axes)
    ici_axes = dict(ici_axes)
    n = len(devices)
    n_dcn = int(np.prod(list(dcn_axes.values())))
    if n_dcn <= 0 or n % n_dcn != 0:
        raise ValueError(
            f"dcn axes {dcn_axes} do not divide {n} devices")
    per_slice = n // n_dcn
    wild = [k for k, v in ici_axes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError("at most one ici axis may be -1")
    if wild:
        known = int(np.prod([v for v in ici_axes.values() if v != -1]))
        if known <= 0 or per_slice % known != 0:
            raise ValueError(
                f"ici axes {ici_axes}: {per_slice} per-slice devices not "
                f"divisible by {known}")
        ici_axes[wild[0]] = per_slice // known
    if int(np.prod(list(ici_axes.values()))) != per_slice:
        raise ValueError(
            f"ici axes {ici_axes} must cover {per_slice} devices/slice")

    names = tuple(dcn_axes) + tuple(ici_axes)
    shape = tuple(dcn_axes.values()) + tuple(ici_axes.values())
    if num_slices(devices) == n_dcn and n_dcn > 1:
        from jax.experimental import mesh_utils
        # same-length shape vectors: each dim is either a DCN or ICI dim
        ici_shape = (1,) * len(dcn_axes) + tuple(ici_axes.values())
        dcn_shape = tuple(dcn_axes.values()) + (1,) * len(ici_axes)
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
        return Mesh(arr, names)
    # synthetic slices: contiguous groups (device order is host order,
    # which keeps intra-group collectives local on multi-process CPU too)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, names)


def multislice_data_spec(mesh: Mesh, dcn_axis: str = "dcn",
                         dp_axis: str = DP) -> PartitionSpec:
    """Batch spec sharding over (dcn, dp) jointly — the hierarchical
    data-parallel layout."""
    axes = tuple(a for a in (dcn_axis, dp_axis) if a in mesh.shape)
    return PartitionSpec(axes if len(axes) > 1 else axes[0])


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    devs = jax.devices()[:n] if n else jax.devices()
    return create_mesh({DP: len(devs)}, devs)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = DP) -> NamedSharding:
    """Shard leading (batch) dim over the data axis."""
    return NamedSharding(mesh, PartitionSpec(axis))
