"""Test harness setup.

Tests run on a virtual 8-device CPU mesh (`--xla_force_host_platform_device_count=8`), which
makes TP/FSDP/SP logic single-process unit-testable — strictly stronger than the reference's
torchrun-subprocess multi-GPU tests (SURVEY §4).

`JAX_PLATFORMS` defaults to `cpu` here. What needs the chip runs there through
`chip_smoke.py`; the TPU compiler is exercised here, without a chip, by
`tests/ops/test_tpu_compile.py`.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
# CPU tests are compile-dominated and throw the compiled code away after a few calls;
# skipping XLA's backend optimization passes cuts the sharded suite ~2.4x with every
# parity/bitwise test still green (both sides of every comparison compile at the same
# level). Override by putting the flag in XLA_FLAGS yourself.
if "xla_backend_optimization_level" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ["XLA_FLAGS"] + " --xla_backend_optimization_level=0"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture()
def mesh_2x2x2(eight_devices):
    """(dp=2, fsdp=2, tp=2) mesh for distributed-logic tests."""
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    MeshManager(
        tensor_parallel_size=2,
        data_parallel_replication_world_size=2,
        data_parallel_sharding_world_size=2,
    )
    yield MeshManager.get_mesh()
    MeshManager.destroy()


@pytest.fixture()
def mesh_fsdp8(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    MeshManager()
    yield MeshManager.get_mesh()
    MeshManager.destroy()
