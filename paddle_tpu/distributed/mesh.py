"""ProcessMesh + Placement + shard_tensor/reshard — the semi-auto parallel
API. ≙ reference «python/paddle/distributed/auto_parallel/» (`shard_tensor`,
`Placement` = Shard/Replicate/Partial, `ProcessMesh`) and the C++ reshard
machinery «paddle/phi/core/distributed/auto_parallel/» (SURVEY.md §2.3).

TPU-native mapping (this IS GSPMD): ProcessMesh wraps jax.sharding.Mesh;
placements lower to a NamedSharding PartitionSpec; 'completion' (sharding
propagation through ops) is XLA's sharding propagation pass, so there is no
per-op SPMD-rule table to maintain — the rules live in the compiler.
`reshard` = device_put / with_sharding_constraint, and XLA inserts the
collectives (SURVEY.md §5 'Distributed communication backend')."""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Parameter, Tensor


# -- placements --------------------------------------------------------------
class Placement:
    pass


class Replicate(Placement):
    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("Replicate")


class Shard(Placement):
    def __init__(self, dim: int):
        self.dim = int(dim)

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("Shard", self.dim))


class Partial(Placement):
    """Pending-reduction placement. GSPMD materializes partial sums inside
    the compiled program; an explicit eager Partial tensor is reduced on
    construction (sum), matching reference reshard p->r semantics."""

    def __init__(self, reduce_type: str = "sum"):
        self.reduce_type = reduce_type

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, other):
        return isinstance(other, Partial) and \
            other.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("Partial", self.reduce_type))


class ReduceType:
    kRedSum = "sum"
    kRedMax = "max"
    kRedMin = "min"
    kRedProd = "prod"
    kRedAvg = "avg"


# -- process mesh ------------------------------------------------------------
class ProcessMesh:
    """≙ paddle.distributed.ProcessMesh — an N-D logical device mesh with
    named axes, wrapping jax.sharding.Mesh.

    On real hardware, axis order should put the fastest-varying (innermost)
    axis on ICI-adjacent devices; jax mesh_utils handles the physical layout
    when constructed via `create_mesh`."""

    def __init__(self, mesh=None, dim_names: Sequence[str] | None = None,
                 shape: Sequence[int] | None = None,
                 process_ids: Sequence[int] | None = None):
        devices = np.asarray(jax.devices())
        if mesh is not None and not isinstance(mesh, (list, tuple, np.ndarray)):
            # already a jax Mesh
            self._jax_mesh = mesh
            self._shape = tuple(mesh.devices.shape)
            self._dim_names = tuple(mesh.axis_names)
            return
        if mesh is not None:
            arr = np.asarray(mesh)
            shape = arr.shape
            process_ids = arr.reshape(-1)
        if shape is None:
            shape = (len(devices),)
        shape = tuple(int(s) for s in shape)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(len(shape))]
        self._dim_names = tuple(dim_names)
        self._shape = shape
        if process_ids is not None:
            dev_arr = devices[np.asarray(process_ids).reshape(shape)]
        else:
            n = int(np.prod(shape))
            dev_arr = devices[:n].reshape(shape)
        self._jax_mesh = Mesh(dev_arr, self._dim_names)

    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    @property
    def shape(self) -> list:
        return list(self._shape)

    @property
    def dim_names(self) -> list:
        return list(self._dim_names)

    @property
    def ndim(self) -> int:
        return len(self._shape)

    @property
    def process_ids(self) -> list:
        return [d.id for d in self._jax_mesh.devices.reshape(-1)]

    def get_dim_size(self, name: str) -> int:
        return self._shape[self._dim_names.index(name)]

    def get_rank_by_dim_and_process_id(self, dim, process_id):
        idx = self.process_ids.index(process_id)
        coord = np.unravel_index(idx, self._shape)
        return coord[self._dim_names.index(dim) if isinstance(dim, str)
                     else dim]

    def __eq__(self, other):
        return isinstance(other, ProcessMesh) and \
            self._shape == other._shape and \
            self._dim_names == other._dim_names

    def __repr__(self):
        return f"ProcessMesh(shape={self.shape}, dim_names={self.dim_names})"


def create_mesh(shape_dict: dict[str, int] | None = None, **axes) -> ProcessMesh:
    """Build a ProcessMesh with ICI-friendly device order via mesh_utils."""
    from jax.experimental import mesh_utils
    axes = dict(shape_dict or {}, **axes)
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    try:
        dev_arr = mesh_utils.create_device_mesh(shape)
    except Exception:
        dev_arr = np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(
            shape)
    return ProcessMesh(Mesh(dev_arr, names))


def create_hybrid_mesh(dcn_axes: dict[str, int] | None = None,
                       ici_axes: dict[str, int] | None = None,
                       devices=None) -> ProcessMesh:
    """Multi-slice mesh: `dcn_axes` are the OUTER (slow) axes that cross
    slice/host boundaries over DCN; `ici_axes` are the inner axes laid out
    on the ICI torus within each slice. ≙ the reference fleet's multi-node
    topology mapping (SURVEY §2.3 hybrid topology; §5 comm backend — "ICI
    vs DCN from mesh axis placement").

    On real multi-slice hardware this routes through
    `mesh_utils.create_hybrid_device_mesh`, which groups devices by
    slice_index so only the dcn axes ride DCN. On a single slice (or the
    CPU test platform) it factors the flat device list with the dcn axes
    slowest-varying — the same logical mesh, so shardings and collectives
    written against it are placement-portable.

    >>> mesh = create_hybrid_mesh(dcn_axes={"dp": 2}, ici_axes={"mp": 4})
    >>> mesh.dim_names     # ['dp', 'mp'] — shard batch over dp: only data
    ...                    # gradients' all-reduce crosses DCN
    """
    from jax.experimental import mesh_utils
    dcn_axes = dict(dcn_axes or {})
    ici_axes = dict(ici_axes or {})
    if not dcn_axes or not ici_axes:
        raise ValueError("create_hybrid_mesh needs both dcn_axes and "
                         "ici_axes (use create_mesh for a flat mesh)")
    names = tuple(dcn_axes) + tuple(ici_axes)
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate axis name across dcn/ici: {names}")
    dcn_shape = tuple(dcn_axes.values())
    ici_shape = tuple(ici_axes.values())
    devs = list(devices if devices is not None else jax.devices())
    n_dcn = int(np.prod(dcn_shape))
    n_ici = int(np.prod(ici_shape))
    if n_dcn * n_ici > len(devs):
        raise ValueError(f"hybrid mesh needs {n_dcn * n_ici} devices, "
                         f"have {len(devs)}")
    slice_ids = sorted({getattr(d, "slice_index", 0) for d in devs})
    if len(slice_ids) > 1:
        # real multi-slice: pick whole slices and the same number of
        # chips from each (a flat prefix could split slices unevenly and
        # fail mesh_utils' per-granule device-count check)
        if len(slice_ids) < n_dcn:
            raise ValueError(
                f"hybrid mesh dcn axes need {n_dcn} slices, hardware has "
                f"{len(slice_ids)}")
        picked = []
        for sid in slice_ids[:n_dcn]:
            in_slice = [d for d in devs
                        if getattr(d, "slice_index", 0) == sid]
            if len(in_slice) < n_ici:
                raise ValueError(
                    f"hybrid mesh ici axes need {n_ici} chips per slice, "
                    f"slice {sid} has {len(in_slice)}")
            picked.extend(in_slice[:n_ici])
        # per-axis (ici, dcn) factor pairs — dcn axes contribute only to
        # the dcn factor, ici axes only to ici
        mesh_shape = (1,) * len(dcn_shape) + ici_shape
        dcn_mesh_shape = dcn_shape + (1,) * len(ici_shape)
        dev_arr = mesh_utils.create_hybrid_device_mesh(
            mesh_shape, dcn_mesh_shape, devices=picked,
            allow_split_physical_axes=True).reshape(dcn_shape + ici_shape)
    else:
        # single slice / CPU: contiguous device ids form a "slice" for
        # each dcn coordinate (outer axes slowest-varying)
        dev_arr = np.asarray(devs[:n_dcn * n_ici]).reshape(
            dcn_shape + ici_shape)
    return ProcessMesh(Mesh(dev_arr, names))


# -- serving tensor-parallel trace context -----------------------------------
# The serving engine (models/serving.py, submesh= mode) sets this around
# its jit DISPATCH calls so sharding constraints inside model code
# (llama.py `_tp_repl`) see the replica's submesh at TRACE time — jit
# traces on the first call, so scoping the call scopes the trace. It is
# deliberately NOT the training `_current_mesh`: a process hosts many
# serving replicas on DISJOINT submeshes, and a global training mesh
# must never leak into a replica's compiled programs (or vice versa).
_serving_tp = None


def serving_tp():
    """The active serving-TP context (a `serving.submesh.SubMesh`), or
    None outside an engine's TP dispatch scope."""
    return _serving_tp


@contextlib.contextmanager
def serving_tp_scope(ctx):
    """Scope a serving replica's TP submesh over a jit dispatch (and
    therefore over any trace it triggers)."""
    global _serving_tp
    prev = _serving_tp
    _serving_tp = ctx
    try:
        yield ctx
    finally:
        _serving_tp = prev


def serving_tp_replicate(value):
    """Constrain a traced value REPLICATED over the active serving-TP
    submesh — the determinism fence of the exact TP mode: placed before
    every row matmul (o_proj / down_proj) and the sampling argmax, it
    forces an all-gather instead of a partial-sum all-reduce, so no
    cross-device reduction ever changes float accumulation order and
    greedy outputs stay bit-identical to tp=1. No-op without an active
    context, or when the context's mode allows row-parallel reductions
    (`replicate_rows` False)."""
    ctx = _serving_tp
    if ctx is None or not getattr(ctx, "replicate_rows", True):
        return value
    return jax.lax.with_sharding_constraint(
        value, NamedSharding(ctx.jax_mesh, PartitionSpec()))


# -- current mesh context ----------------------------------------------------
_current_mesh: Optional[ProcessMesh] = None


def get_mesh() -> Optional[ProcessMesh]:
    return _current_mesh


def set_mesh(mesh: ProcessMesh | None):
    global _current_mesh
    _current_mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: ProcessMesh):
    global _current_mesh
    prev = _current_mesh
    _current_mesh = mesh
    try:
        yield mesh
    finally:
        _current_mesh = prev


# -- Pallas kernels under a mesh ---------------------------------------------
# Mosaic refuses to partition a kernel ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map"): a
# bare pallas_call inside a jit that spans more than one device does
# not lower for the TPU. The interpret mode the CPU mesh runs is plain
# XLA and partitions silently, so only a multi-chip TPU shows it.
# What a tensor dimension means decides how it may be split; each role
# names the mesh axes that carry it under `shard_llama`-style placements.
_KERNEL_ROLE_AXES = {"batch": ("dp", "sharding"), "seq": ("sep",),
                     "heads": ("mp",)}


def _kernel_mesh():
    """(jax Mesh, {role: mesh axes}) a Pallas call must be split over,
    or None when it may run bare: no multi-device mesh is active, or
    the caller is already inside a shard_map (pipeline stages, ring
    attention, expert dispatch run their kernels per shard)."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    tp = _serving_tp
    if tp is not None:
        # a serving replica's one-axis submesh shards heads only
        if tp.jax_mesh.size == 1:
            return None
        return tp.jax_mesh, {"heads": tuple(tp.jax_mesh.axis_names)}
    m = _current_mesh
    if m is None or m.jax_mesh.size == 1:
        return None
    return m.jax_mesh, {
        role: tuple(a for a in axes if a in m.dim_names)
        for role, axes in _KERNEL_ROLE_AXES.items()}


def shard_kernel(fn, args, in_roles, out_roles):
    """`fn(*args)` for an `fn` whose body is a Pallas TPU kernel, made
    to compile under the active mesh (training `use_mesh` or a serving
    replica's TP scope): `shard_map`ped so every device runs the kernel
    on its own shard. `in_roles` gives, per argument, one role per
    tensor dimension — "batch", "seq", "heads" or None — and
    `out_roles` the same for the single output. A role is split over
    its mesh axes only when EVERY dimension carrying it divides;
    otherwise, like None, it stays whole on each device (the kernel
    then repeats that work per device, which is what GSPMD does with a
    replicated operand). With no multi-device mesh active this is
    exactly `fn(*args)`. The mesh is read while TRACING and, like
    `serving_tp_replicate`'s, is not part of jit's cache key: trace a
    function under the mesh it will run on."""
    ctx = _kernel_mesh()
    if ctx is None:
        return fn(*args)
    mesh, role_axes = ctx

    def ways(role):
        return int(np.prod([mesh.shape[a]
                            for a in role_axes.get(role, ())]))

    split = {}
    for roles, a in zip(in_roles, args):
        for n, role in zip(a.shape, roles):
            if role is not None:
                split[role] = (split.get(role, True) and ways(role) > 1
                               and n % ways(role) == 0)

    def spec(roles):
        return PartitionSpec(*[role_axes[r] if r is not None and split[r]
                               else None for r in roles])

    # check_vma off: pallas_call cannot annotate varying-mesh-axes on
    # its outputs; the specs above are exact
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(r) for r in in_roles),
                         out_specs=spec(out_roles),
                         check_vma=False)(*args)


# -- placement -> PartitionSpec ---------------------------------------------
def placements_to_spec(placements: Sequence[Placement],
                       mesh: ProcessMesh) -> PartitionSpec:
    """One placement per mesh dim -> PartitionSpec over tensor dims."""
    by_tensor_dim: dict[int, list[str]] = {}
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            by_tensor_dim.setdefault(pl.dim, []).append(
                mesh.dim_names[mesh_dim])
    if not by_tensor_dim:
        return PartitionSpec()
    max_dim = max(by_tensor_dim)
    entries = []
    for d in range(max_dim + 1):
        axes = by_tensor_dim.get(d)
        if axes is None:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    return PartitionSpec(*entries)


def spec_to_placements(spec: PartitionSpec, mesh: ProcessMesh,
                       ndim: int) -> list[Placement]:
    placements: list[Placement] = [Replicate() for _ in mesh.dim_names]
    for tdim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            placements[mesh.dim_names.index(ax)] = Shard(tdim)
    return placements


# -- shard_tensor / reshard --------------------------------------------------
def _is_tracing(value) -> bool:
    return not isinstance(value, jax.Array) or isinstance(
        value, jax.core.Tracer)


def shard_tensor(x, mesh: ProcessMesh, placements: Sequence[Placement],
                 stop_gradient: bool | None = None) -> Tensor:
    """≙ paddle.distributed.shard_tensor: place a tensor on the mesh.
    Eager: device_put with NamedSharding (physically distributes).
    Traced: with_sharding_constraint (GSPMD annotation)."""
    from ..core.tensor import to_tensor
    t = x if isinstance(x, Tensor) else to_tensor(x)
    spec = placements_to_spec(placements, mesh)
    sharding = NamedSharding(mesh.jax_mesh, spec)
    partial_axes = [mesh.dim_names[i] for i, p in enumerate(placements)
                    if isinstance(p, Partial)]
    v = t._value
    if partial_axes:
        # eager partial tensors are immediately reduced (p->r reshard)
        pass  # values arriving here are already global; nothing to sum
    if isinstance(v, jax.core.Tracer):
        v = jax.lax.with_sharding_constraint(v, sharding)
    else:
        v = jax.device_put(v, sharding)
    if isinstance(t, Parameter):
        out = Parameter(v, trainable=not t.stop_gradient, name=t.name)
    else:
        out = Tensor(v, stop_gradient=t.stop_gradient if stop_gradient is None
                     else stop_gradient, name=t.name)
        out._node, out._out_index = t._node, t._out_index
    out.dist_attr = (mesh, list(placements))
    return out


def dtensor_from_local(x, mesh, placements):
    return shard_tensor(x, mesh, placements)


def reshard(x: Tensor, mesh: ProcessMesh,
            placements: Sequence[Placement]) -> Tensor:
    """≙ paddle.distributed.reshard: convert between placements; XLA emits
    the all-gather/all-to-all/reduce-scatter this implies."""
    return shard_tensor(x, mesh, placements)


def shard_layer(layer, process_mesh: ProcessMesh, shard_fn=None,
                input_fn=None, output_fn=None):
    """≙ paddle.distributed.shard_layer: apply shard_fn(name, layer, mesh)
    to every sublayer (default: replicate all params)."""
    def default_fn(name, sublayer, mesh):
        for pname, p in list(sublayer._parameters.items()):
            if p is not None:
                sharded = shard_tensor(
                    p, mesh, [Replicate() for _ in mesh.dim_names])
                p._value = sharded._value
                p.dist_attr = sharded.dist_attr
    fn = shard_fn or default_fn
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    return layer


def shard_constraint(value, *axis_names, mesh: ProcessMesh | None = None):
    """Annotate a traced jnp value (inside jit) with a sharding constraint;
    no-op when no mesh is active. Helper for model code."""
    m = mesh or get_mesh()
    if m is None:
        return value
    spec = PartitionSpec(*[a if a is None else a for a in axis_names])
    try:
        return jax.lax.with_sharding_constraint(
            value, NamedSharding(m.jax_mesh, spec))
    except ValueError:
        return value


def local_map(fn, out_placements, in_placements, process_mesh,
              reshard_inputs=False):
    """≙ paddle.distributed.local_map — run fn on local shards via shard_map."""
    from jax import shard_map
    in_specs = tuple(placements_to_spec(p, process_mesh)
                     for p in in_placements)
    out_specs = tuple(placements_to_spec(p, process_mesh)
                      for p in out_placements)
    if len(out_specs) == 1:
        out_specs = out_specs[0]
    mapped = shard_map(fn, mesh=process_mesh.jax_mesh, in_specs=in_specs,
                       out_specs=out_specs)

    def wrapper(*tensors):
        vals = [t._value if isinstance(t, Tensor) else t for t in tensors]
        out = mapped(*vals)
        return jax.tree_util.tree_map(Tensor, out)
    return wrapper
