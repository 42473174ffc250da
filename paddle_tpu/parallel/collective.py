"""Collective communication ops.

TPU-native replacement for the reference's collective operator family
(/root/reference/paddle/fluid/operators/collective/: c_allreduce_op.h:72
(ring_id keyed), c_broadcast_op.cc, c_allgather_op.cc, c_reducescatter_op.cc,
c_scatter_op.cc; comm registry platform/collective_helper.h:62
NCCLCommContext). The NCCL ring becomes a **mesh axis**: a
:class:`CommGroup` names a set of axes (the ring_id analogue), and each
collective lowers to the XLA ICI/DCN primitive via jax.lax inside
shard_map/pjit-traced code. Outside traced code, the same API falls back to
single-process semantics (identity), matching the reference's behavior with
world_size=1.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics as _obs_metrics

AxisName = Union[str, Sequence[str]]


def _account(op: str, x) -> None:
    """Count collective launches + payload bytes. Runs in host Python:
    inside shard_map/pjit that is ONCE per trace (compiled steady state
    pays nothing), eagerly it is per call — both gated on
    FLAGS_enable_metrics."""
    if not _obs_metrics.enabled():
        return
    _obs_metrics.counter("collective_calls_total",
                         "collective ops (per trace when jitted)"
                         ).inc(op=op)
    try:
        nbytes = sum(int(l.size) * int(l.dtype.itemsize)
                     for l in jax.tree.leaves(x))
    except (AttributeError, TypeError):
        nbytes = 0
    _obs_metrics.counter("collective_bytes_total",
                         "payload bytes handed to collectives "
                         "(per trace when jitted)").inc(nbytes, op=op)

# ring_id → axis-name registry (ref: NCCLCommContext keyed by ring_id,
# collective_helper.h:62)
_groups: Dict[int, "CommGroup"] = {}


class CommGroup:
    """A named communicator (≈ one NCCL ring)."""

    def __init__(self, ring_id: int, axis: AxisName) -> None:
        self.ring_id = ring_id
        self.axis = axis

    def __repr__(self) -> str:
        return f"CommGroup(ring_id={self.ring_id}, axis={self.axis!r})"


def new_group(axis: AxisName, ring_id: Optional[int] = None) -> CommGroup:
    """(ref: c_comm_init_op.cc) register a communicator over mesh axes."""
    rid = ring_id if ring_id is not None else (max(_groups) + 1
                                               if _groups else 0)
    g = CommGroup(rid, axis)
    _groups[rid] = g
    return g


def get_group(ring_id: int = 0) -> CommGroup:
    if ring_id not in _groups:
        _groups[ring_id] = CommGroup(ring_id, "dp")
    return _groups[ring_id]


def _axis(group: Optional[Union[CommGroup, AxisName]]) -> AxisName:
    if group is None:
        return get_group(0).axis
    if isinstance(group, CommGroup):
        return group.axis
    return group


def _axis_size(axis: AxisName) -> int:
    return lax.axis_size(axis)


def _in_traced_collective(axis: AxisName) -> bool:
    try:
        _axis_size(axis)
        return True
    except (NameError, KeyError, Exception):
        return False


def all_reduce(x, op: str = "sum", group=None):
    """(ref: c_allreduce_op.h:72; kernels :105 call ncclAllReduce)."""
    axis = _axis(group)
    if not _in_traced_collective(axis):
        return x
    _account("all_reduce", x)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "prod":
        return jnp.exp(lax.psum(jnp.log(x), axis))
    raise ValueError(f"unknown reduce op '{op}'")


def all_gather(x, axis: int = 0, group=None):
    """(ref: c_allgather_op.cc)."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("all_gather", x)
    return lax.all_gather(x, a, axis=axis, tiled=True)


def reduce_scatter(x, axis: int = 0, group=None):
    """(ref: c_reducescatter_op.cc)."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("reduce_scatter", x)
    return lax.psum_scatter(x, a, scatter_dimension=axis, tiled=True)


def broadcast(x, src: int = 0, group=None):
    """(ref: c_broadcast_op.cc) — take src's shard everywhere."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("broadcast", x)
    n = _axis_size(a)
    return lax.all_gather(x, a)[src] if n > 1 else x

def reduce(x, dst: int = 0, op: str = "sum", group=None):
    """(ref: c_reduce_op.h) — result valid on dst, others get the
    reduction too (psum); matches capability, XLA has no cheaper reduce."""
    return all_reduce(x, op, group)


def scatter(x, src: int = 0, group=None):
    """(ref: c_scatter_op.cc) — each rank takes its slice of src's value."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("scatter", x)
    n = _axis_size(a)
    idx = lax.axis_index(a)
    full = lax.all_gather(x, a)[src]
    size = full.shape[0] // n
    return lax.dynamic_slice_in_dim(full, idx * size, size, axis=0)


def all_to_all(x, split_axis: int = 0, concat_axis: int = 0, group=None):
    """(ref capability: alltoall in later fleet; needed for Ulysses SP/EP)."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("all_to_all", x)
    return lax.all_to_all(x, a, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ppermute(x, perm, group=None):
    """Ring shift primitive (ring attention building block)."""
    a = _axis(group)
    if not _in_traced_collective(a):
        return x
    _account("ppermute", x)
    return lax.ppermute(x, a, perm)


def barrier(group=None):
    """(ref: barrier via gloo GlooWrapper::Barrier gloo_wrapper.h:146).
    In traced code a psum serves as a barrier; eagerly it's a no-op in
    single-process, jax.distributed-level barrier otherwise."""
    a = _axis(group)
    if _in_traced_collective(a):
        return lax.psum(jnp.ones(()), a)
    try:
        import jax._src.distributed as dist
        if dist.global_state.client is not None:
            dist.global_state.client.wait_at_barrier("paddle_tpu_barrier",
                                                     60_000)
    # ptlint: disable=silent-failure -- jax._src.distributed is a private API probed opportunistically; without it the psum below is still a barrier
    except Exception:
        pass
    return jnp.ones(())


def rank(group=None):
    a = _axis(group)
    if _in_traced_collective(a):
        return lax.axis_index(a)
    return jnp.zeros((), jnp.int32)


def world_size(group=None) -> int:
    a = _axis(group)
    if _in_traced_collective(a):
        return _axis_size(a)
    return 1
