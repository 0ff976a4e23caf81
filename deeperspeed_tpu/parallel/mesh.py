"""Topology → `jax.sharding.Mesh` lowering and the mpu-style grid object.

This replaces the reference's `PipelineParallelGrid`
(`deepspeed/runtime/pipe/topology.py:257-466`): where the reference builds
torch `ProcessGroup`s per dp/pp/mp/slice axis, here each topology axis
becomes a named mesh axis and XLA derives the collective groups from
sharding specs. The grid keeps the same accessor API so engine code (and
external Megatron-style callers) can stay mpu-agnostic.

Canonical axis names: ``pipe``, ``data``, ``model`` (matching the reference
topology names). ZeRO shards over ``data``; tensor parallelism over
``model``; the pipeline executor ppermutes over ``pipe``.
"""

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .topology import ProcessTopology, default_topology

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"


def build_mesh(topology=None, devices=None, axes=None, dims=None):
    """Build a Mesh whose linear device order matches the topology's
    row-major rank order, so topology rank i == mesh device i."""
    if devices is None:
        devices = jax.devices()
    if topology is None:
        if axes is None or dims is None:
            topology = default_topology(len(devices))
        else:
            topology = ProcessTopology(axes=axes, dims=dims)
    if topology.world_size() != len(devices):
        raise ValueError(
            f"topology world size {topology.world_size()} != device count "
            f"{len(devices)}")
    dev_array = np.asarray(devices, dtype=object).reshape(topology.dims)
    return Mesh(dev_array, axis_names=tuple(topology.get_axis_names()))


def ambient_auto_mesh():
    """The mesh a traced function runs under, if GSPMD is partitioning it
    over more than one device: the abstract mesh the tracing engine
    declared (`jax.sharding.use_abstract_mesh`; `DeepSpeedEngine._jit`
    does), or None — no mesh declared, one device, or already inside a
    `shard_map` (manual axes), where code runs per shard anyway."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return None
    return mesh


def per_shard(kernel, args, dim_axes):
    """``kernel(*args)`` for a Pallas kernel call under GSPMD.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    a kernel traced under a multi-device mesh runs inside a `shard_map`
    over the dims along which its work is independent. ``dim_axes`` maps
    an array dim to the canonical mesh axis that may shard it —
    ``{0: DATA_AXIS, 2: MODEL_AXIS}`` for [B, S, H, D] attention; every
    arg (and the one output) is split on those of its dims that exist
    and divide evenly, and replicated otherwise. With no multi-device
    mesh ambient (`ambient_auto_mesh`) this is the plain call."""
    mesh = ambient_auto_mesh()
    if mesh is None:
        return kernel(*args)
    shape = args[0].shape
    split = {dim: axis for dim, axis in dim_axes.items()
             if axis in mesh.axis_names and mesh.shape[axis] > 1
             and shape[dim] % mesh.shape[axis] == 0}
    if not split:
        return kernel(*args)

    def spec(x):
        return PartitionSpec(*[split.get(d) if d < x.ndim and
                               x.shape[d] == shape[d] else None
                               for d in range(x.ndim)])

    return jax.shard_map(kernel, in_specs=tuple(spec(a) for a in args),
                         out_specs=spec(args[0]), check_vma=False)(*args)


def data_parallel_sharding(mesh, spec=None):
    """Sharding for a batch: leading dim split over every data-like axis."""
    if spec is None:
        spec = PartitionSpec(mesh.axis_names[-1] if DATA_AXIS not in
                             mesh.axis_names else DATA_AXIS)
    return NamedSharding(mesh, spec)


class PipelineParallelGrid:
    """mpu-compatible view of a device mesh.

    Exposes the same accessors as the reference grid
    (`get_data_parallel_world_size`, `get_pipe_parallel_rank`, ...) but
    groups are mesh axes rather than torch process groups. "Ranks" here are
    *chips* (mesh positions); with multi-host meshes the local process sees
    only its addressable shard of each array, which XLA manages.
    """

    def __init__(self, topology=None, devices=None, rank=0):
        if devices is None:
            devices = jax.devices()
        self._topo = topology if topology is not None else \
            default_topology(len(devices))
        self.global_rank = rank
        self.world_size = self._topo.world_size()
        if self.world_size != len(devices):
            raise ValueError(
                f"topology world size {self.world_size} != device count "
                f"{len(devices)}")

        self.mesh = build_mesh(self._topo, devices)

        self.data_parallel_size = max(self._topo.get_dim(DATA_AXIS), 1)
        self.pipe_parallel_size = max(self._topo.get_dim(PIPE_AXIS), 1)
        self.model_parallel_size = max(self._topo.get_dim(MODEL_AXIS), 1)

        self.stage_id = self.get_stage_id()
        self.data_parallel_id = self.get_data_parallel_id()
        self.is_first_stage = self.stage_id == 0
        self.is_last_stage = self.stage_id == self.pipe_parallel_size - 1

        # Rank lists per axis, kept for checkpoint naming and debugging.
        self.dp_groups = self._topo.get_axis_comm_lists(DATA_AXIS)
        self.pipe_groups = self._topo.get_axis_comm_lists(PIPE_AXIS)
        self.model_groups = self._topo.get_axis_comm_lists(MODEL_AXIS)
        self.p2p_groups = self._build_p2p_groups()

    def _coord(self):
        return self._topo.get_coord(self.global_rank)

    def get_stage_id(self):
        coord = self._coord()
        return getattr(coord, PIPE_AXIS, 0) if PIPE_AXIS in self._topo.axes \
            else 0

    def get_data_parallel_id(self):
        coord = self._coord()
        return getattr(coord, DATA_AXIS, 0) if DATA_AXIS in self._topo.axes \
            else 0

    def _build_p2p_groups(self):
        """[rank, next-stage buddy] pairs along the pipe axis, wrapping at the
        last stage (reference `topology.py:381-396`)."""
        comm_lists = self._topo.get_axis_comm_lists(PIPE_AXIS)
        if not comm_lists:
            return [[r, r] for r in range(self.world_size)]
        p2p_lists = []
        for rank in range(self.world_size):
            for ranks in comm_lists:
                if rank in ranks:
                    idx = ranks.index(rank)
                    buddy = ranks[(idx + 1) % self.pipe_parallel_size]
                    p2p_lists.append([rank, buddy])
                    break
        return p2p_lists

    def stage_to_global(self, stage_id, **kwargs):
        me = self._coord()
        transform = me._replace(**{PIPE_AXIS: stage_id}, **kwargs)._asdict()
        return self._topo.get_rank(**transform)

    def topology(self):
        return self._topo

    # mpu-style accessors -------------------------------------------------

    def get_global_rank(self):
        return self.global_rank

    def get_pipe_parallel_rank(self):
        return self.get_stage_id()

    def get_pipe_parallel_world_size(self):
        return self.pipe_parallel_size

    def get_pipe_parallel_group(self):
        return PIPE_AXIS

    def get_data_parallel_rank(self):
        return self.data_parallel_id

    def get_data_parallel_world_size(self):
        return self.data_parallel_size

    def get_data_parallel_group(self):
        return DATA_AXIS

    def get_data_parallel_src_rank(self):
        return (self.global_rank // self.data_parallel_size) * \
            self.data_parallel_size

    # "model parallel" in the reference engine sense: everything that is not
    # data parallel (pipe × tensor slicing), used for overflow checks.
    def get_model_parallel_rank(self):
        ranks = sorted(self._topo.get_axis_list(DATA_AXIS,
                                                self.data_parallel_id))
        return ranks.index(self.global_rank)

    def get_model_parallel_world_size(self):
        return self.world_size // self.data_parallel_size

    def get_model_parallel_group(self):
        return tuple(a for a in self._topo.axes if a != DATA_AXIS)

    # Megatron-style tensor slicing axis.
    def get_slice_parallel_rank(self):
        coord = self._coord()
        return getattr(coord, MODEL_AXIS, 0) if MODEL_AXIS in self._topo.axes \
            else 0

    def get_slice_parallel_world_size(self):
        return self.model_parallel_size

    def get_slice_parallel_group(self):
        return MODEL_AXIS
