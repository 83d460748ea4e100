"""Eager collective API. ≙ reference
«python/paddle/distributed/communication/» over ProcessGroupNCCL
(SURVEY.md §2.3 'Collective API').

TPU-native contract (single-controller SPMD): the reference is
multi-controller — each rank holds a LOCAL tensor and collectives combine
them over NCCL. Here, the per-rank tensors of a group are represented as ONE
global array whose leading axis is the group axis, sharded over the mesh;
each collective is a `shard_map`ped `lax.p*` over that axis, which is exactly
the collective XLA emits over ICI. `Group.stack()` / `Group.unstack()`
convert between the two views. Real training code rarely calls these — GSPMD
inserts collectives automatically; this module exists for API parity, tests,
and custom shard_map code."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from jax import shard_map

from ..core.tensor import Tensor, to_tensor
from .mesh import ProcessMesh


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator = one axis of a (possibly 1-D) device mesh.
    ≙ reference ProcessGroup («paddle/fluid/distributed/collective/») [U]."""

    def __init__(self, mesh: ProcessMesh, axis: str, group_id: int = 0):
        self.mesh = mesh
        self.axis = axis
        self.id = group_id

    @property
    def nranks(self) -> int:
        return self.mesh.get_dim_size(self.axis)

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self) -> int:
        return 0  # single-controller: queries are global

    @property
    def ranks(self) -> list:
        return list(range(self.nranks))

    def get_group_rank(self, rank):
        return rank

    # -- view conversion -----------------------------------------------------
    def stack(self, tensors: Sequence[Tensor]) -> Tensor:
        """List of per-rank tensors -> global (nranks, ...) array sharded
        over the group axis."""
        vals = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                for t in tensors]
        stacked = jnp.stack(vals, 0)
        sharding = NamedSharding(self.mesh.jax_mesh,
                                 PartitionSpec(self.axis))
        return Tensor(jax.device_put(stacked, sharding))

    def unstack(self, t: Tensor) -> list:
        return [Tensor(v) for v in t._value]

    def _run(self, fn, t: Tensor, out_spec=None, in_spec=None) -> Tensor:
        v = t._value if isinstance(t, Tensor) else jnp.asarray(t)
        in_specs = in_spec if in_spec is not None else PartitionSpec(self.axis)
        out_specs = out_spec if out_spec is not None \
            else PartitionSpec(self.axis)
        mapped = shard_map(fn, mesh=self.mesh.jax_mesh,
                           in_specs=(in_specs,), out_specs=out_specs,
                           check_vma=False)
        return Tensor(mapped(v))


_default_group: Optional[Group] = None
_group_counter = 0


def _get_group(group: Optional[Group]) -> Group:
    global _default_group
    if group is not None:
        return group
    if _default_group is None:
        n = len(jax.devices())
        mesh = ProcessMesh(shape=(n,), dim_names=("world",))
        _default_group = Group(mesh, "world")
    return _default_group


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    """≙ paddle.distributed.new_group. Builds a 1-D mesh over the given
    device ids (defaults to all)."""
    global _group_counter
    _group_counter += 1
    if ranks is None:
        ranks = list(range(len(jax.devices())))
    mesh = ProcessMesh(shape=(len(ranks),), dim_names=("world",),
                       process_ids=ranks)
    return Group(mesh, "world", _group_counter)


def get_group(gid: int = 0) -> Group:
    return _get_group(None)


# -- collectives over the stacked representation -----------------------------
def all_reduce(tensor: Tensor, op: str = ReduceOp.SUM,
               group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Input: (nranks, ...) stacked view. Output: same shape, every rank
    slice = reduction over ranks."""
    g = _get_group(group)
    red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
           ReduceOp.MIN: jax.lax.pmin,
           ReduceOp.AVG: lambda v, a: jax.lax.pmean(v, a)}[op]

    def fn(v):
        return red(v, g.axis)
    out = g._run(fn, tensor)
    if isinstance(tensor, Tensor):
        tensor._value = out._value
        return tensor
    return out


def all_gather(tensor_list, tensor: Tensor = None,
               group: Optional[Group] = None, sync_op: bool = True):
    """Paddle signature: results appended to tensor_list. Input is the
    stacked (nranks, ...) view; appends each rank's gathered copy."""
    g = _get_group(group)

    def fn(v):
        # tiled concat along the stacked axis; result identical on every
        # shard -> replicated out_spec
        return jax.lax.all_gather(v, g.axis, axis=0, tiled=True)
    out = g._run(fn, tensor, out_spec=PartitionSpec())  # (nranks, ...)
    if tensor_list is not None:
        for i in range(g.nranks):
            tensor_list.append(Tensor(out._value[i]))
        return tensor_list
    return out


def all_gather_object(object_list, obj, group=None):
    # single-controller: every "rank" sees the same object
    g = _get_group(group)
    object_list.extend([obj] * g.nranks)
    return object_list


def reduce_scatter(tensor: Tensor, tensor_list=None, op: str = ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True):
    """Stacked view in (nranks, nranks*chunk, ...) semantics: reduces over
    ranks then scatters chunks."""
    g = _get_group(group)

    def fn(v):
        # v: (1, chunks...) local slice of the stacked axis
        summed = jax.lax.psum(v, g.axis)            # (1, n*chunk)
        idx = jax.lax.axis_index(g.axis)
        chunk = summed.shape[1] // g.nranks
        return jax.lax.dynamic_slice_in_dim(summed, idx * chunk, chunk, 1)
    out = g._run(fn, tensor)
    if isinstance(tensor, Tensor) and tensor_list is None:
        return out
    return out


def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True) -> Tensor:
    g = _get_group(group)

    def fn(v):
        # every rank receives rank-src's slice
        gathered = jax.lax.all_gather(v, g.axis, axis=0)  # (n, 1, ...)
        return gathered[src]
    out = g._run(fn, tensor)
    if isinstance(tensor, Tensor):
        tensor._value = out._value
        return tensor
    return out


def reduce(tensor: Tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    # single-controller: same as all_reduce but only dst slice meaningful
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor: Tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    g = _get_group(group)
    if tensor_list is not None:
        src_stack = g.stack(tensor_list)
        if isinstance(tensor, Tensor):
            tensor._value = src_stack._value
            return tensor
        return src_stack
    return tensor


def alltoall(out_tensor_list, in_tensor_list, group: Optional[Group] = None,
             sync_op: bool = True):
    """in: stacked (n, n, ...) view (rank-major, then destination chunk)."""
    g = _get_group(group)
    if isinstance(in_tensor_list, (list, tuple)):
        stacked = g.stack([t if isinstance(t, Tensor) else to_tensor(t)
                           for t in in_tensor_list])
    else:
        stacked = in_tensor_list

    def fn(v):
        # v: (1, n, ...) — local row; all_to_all swaps axis 1 across ranks
        return jax.lax.all_to_all(v, g.axis, split_axis=1, concat_axis=0,
                                  tiled=False)
    out = g._run(fn, stacked)
    if out_tensor_list is not None:
        val = out._value  # (n, 1, n?, ...) -> recover per-rank rows
        flat = val.reshape((g.nranks, g.nranks) + val.shape[2:]) \
            if val.ndim >= 2 else val
        for i in range(g.nranks):
            out_tensor_list.append(Tensor(flat[i]))
        return out_tensor_list
    return out


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    g = _get_group(group)

    def fn(v):
        n = g.nranks
        chunk = v.shape[1] // n
        v4 = v.reshape((1, n, chunk) + v.shape[2:])
        out = jax.lax.all_to_all(v4, g.axis, split_axis=1, concat_axis=0)
        return out.reshape((1, n * chunk) + v.shape[2:])
    out = g._run(fn, in_tensor)
    if isinstance(out_tensor, Tensor):
        out_tensor._value = out._value
        return out_tensor
    return out


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "eager p2p send/recv has no single-controller equivalent; use "
        "paddle_tpu.distributed.fleet pipeline parallelism (ppermute inside "
        "the compiled program) instead — SURVEY.md §2.3 PP row.")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "see send(); p2p lives inside shard_map as lax.ppermute on TPU.")


def barrier(group=None):
    jax.effects_barrier()


def destroy_process_group(group=None):
    global _default_group
    _default_group = None


def get_backend(group=None) -> str:
    return "xla"  # ICI/DCN collectives emitted by XLA
