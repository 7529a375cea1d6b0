"""MeshManager: the single source of truth for device topology.

Parity: reference `dolomite_engine/utils/parallel.py:34-273` (`ProcessGroupManager`) builds a 2-D
torch `DeviceMesh (dp, tp)` plus a reshaped (replicate, shard) dp mesh for HSDP
(`get_data_parallel_mesh_with_topology`, lines 255-266). The TPU-native design replaces all of
that — NCCL groups, DTensor meshes, FSDP process groups — with ONE `jax.sharding.Mesh` over five
named axes:

    ("dp", "fsdp", "sp", "tp", "ep")

  - dp:   pure replication data parallel (the HSDP "replicate" axis / ZeRO topology replication)
  - fsdp: sharded data parallel (ZeRO / FSDP shard axis; params+opt state sharded here)
  - sp:   sequence/context parallelism for long sequences (ring attention / all-to-all); the
          reference has NO context parallelism (SURVEY §2.6) — first-class here
  - tp:   tensor parallelism (column/row sharding of weights; Megatron-SP activations ride here)
  - ep:   expert parallelism for MoE (reference only TP-shards experts; real EP here)

GSPMD inserts all collectives; axes of size 1 are free. The axis order puts tp innermost so TP
collectives ride the fastest ICI links, and dp outermost so pure replication can cross DCN.
"""

from __future__ import annotations

import math
from contextlib import contextmanager

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MESH_AXES = ("dp", "fsdp", "sp", "tp", "ep")

# data is sharded over every data-parallel-ish axis so per-device batch stays small.
# "ep" is batch-parallel outside MoE blocks (DeepSpeed-style EP within the data-parallel
# dimension): attention/MLP shard the batch over it, MoE layers all_to_all tokens across it.
BATCH_AXES = ("dp", "fsdp", "ep")


class MeshManager:
    """Singleton over the global device mesh (use module-level accessors below)."""

    mesh: Mesh | None = None
    _sizes: dict[str, int] = {}

    def __init__(
        self,
        tensor_parallel_size: int = 1,
        sequence_parallel_size: int = 1,
        expert_parallel_size: int = 1,
        data_parallel_replication_world_size: int | None = None,
        data_parallel_sharding_world_size: int | None = None,
        devices: list | None = None,
    ) -> None:
        devices = jax.devices() if devices is None else devices
        n = len(devices)

        model_parallel = tensor_parallel_size * sequence_parallel_size * expert_parallel_size
        if n % model_parallel != 0:
            raise ValueError(
                f"device count {n} not divisible by tp*sp*ep = {model_parallel}"
            )
        data_parallel_size = n // model_parallel

        # ZeRO topology: split dp into (replicate, shard); default = all sharding
        # (reference `arguments.py:283-297` ZeroTopologyArgs)
        if data_parallel_replication_world_size is None and data_parallel_sharding_world_size is None:
            replicate, shard = 1, data_parallel_size
        else:
            replicate = data_parallel_replication_world_size
            shard = data_parallel_sharding_world_size
            if replicate is None:
                replicate = data_parallel_size // shard
            if shard is None:
                shard = data_parallel_size // replicate
            if replicate * shard != data_parallel_size:
                raise ValueError(
                    f"replication ({replicate}) x sharding ({shard}) != data parallel size "
                    f"({data_parallel_size})"
                )

        shape = (replicate, shard, sequence_parallel_size, tensor_parallel_size, expert_parallel_size)
        if math.prod(shape) != n:
            raise ValueError(f"mesh shape {shape} does not cover {n} devices")

        try:
            device_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except (AssertionError, NotImplementedError, ValueError) as error:
            # shapes the TPU topology helper cannot place (and hand-picked device subsets):
            # row-major assignment is still a valid mesh, but which axis rides which ICI
            # link is then arbitrary — so it is said, not swallowed
            import logging

            from ..utils.logger import log_rank_0

            log_rank_0(
                logging.WARNING,
                f"create_device_mesh{shape} failed ({error!r}); assigning devices row-major",
            )
            device_array = np.asarray(devices).reshape(shape)

        MeshManager.mesh = Mesh(device_array, MESH_AXES)
        MeshManager._sizes = dict(zip(MESH_AXES, shape))

    # ------------------------------------------------------------------ accessors
    @staticmethod
    def get_mesh() -> Mesh:
        if MeshManager.mesh is None:
            raise RuntimeError("MeshManager not initialized")
        return MeshManager.mesh

    @staticmethod
    def is_initialized() -> bool:
        return MeshManager.mesh is not None

    @staticmethod
    def get_global_rank() -> int:
        return jax.process_index()

    @staticmethod
    def get_world_size() -> int:
        return jax.device_count()

    @staticmethod
    def axis_size(axis: str) -> int:
        return MeshManager._sizes.get(axis, 1)

    @staticmethod
    def get_data_parallel_world_size() -> int:
        # ep is batch-parallel outside MoE layers (see BATCH_AXES)
        return (
            MeshManager.axis_size("dp")
            * MeshManager.axis_size("fsdp")
            * MeshManager.axis_size("ep")
        )

    @staticmethod
    def get_tensor_parallel_world_size() -> int:
        return MeshManager.axis_size("tp")

    @staticmethod
    def get_sequence_parallel_world_size() -> int:
        return MeshManager.axis_size("sp")

    @staticmethod
    def get_expert_parallel_world_size() -> int:
        return MeshManager.axis_size("ep")

    @staticmethod
    def destroy() -> None:
        MeshManager.mesh = None
        MeshManager._sizes = {}


def get_mesh() -> Mesh:
    return MeshManager.get_mesh()


def make_default_mesh(**kwargs) -> Mesh:
    """Build (or rebuild) the global mesh; returns it."""
    MeshManager(**kwargs)
    return MeshManager.get_mesh()


@contextmanager
def temporary_mesh(mesh: Mesh):
    """Swap the global mesh (tests; reference's dummy tp-rank context managers
    `utils/parallel.py:140-192` have no JAX analogue since building is SPMD-global)."""
    old_mesh, old_sizes = MeshManager.mesh, MeshManager._sizes
    MeshManager.mesh = mesh
    MeshManager._sizes = {name: int(size) for name, size in zip(mesh.axis_names, mesh.devices.shape)}
    try:
        yield mesh
    finally:
        MeshManager.mesh, MeshManager._sizes = old_mesh, old_sizes


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


def batch_sharding() -> NamedSharding:
    """Sharding for a [batch, ...] host array: batch split over all data axes."""
    return named_sharding(BATCH_AXES)
