"""Production mesh construction.

Defined as functions (not module constants) so importing never touches jax
device state. The dry run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import (see dryrun.py); real deployments get the same shapes
from the TPU runtime.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the CoCoA+ shard_map rounds need untyped (Auto) axes;
    # jax.make_mesh defaults to Explicit
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess-based integration tests."""
    return _auto_mesh(shape, axes)


def initialize_distributed(coordinator: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None):
    """Multi-host bring-up for real pods.

    On Cloud TPU, `jax.distributed.initialize()` autodetects everything from
    the TPU metadata service; on other clusters pass the coordinator address
    + process topology explicitly (or set JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID). Call BEFORE any other jax API, then
    build meshes with make_production_mesh() -- jax.devices() spans all hosts
    afterwards. Nothing in this package calls it yet: `dryrun` builds its
    meshes with this module's helpers on the devices of one process.

    Returns (process_index, process_count)."""
    import os
    if coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize(
            coordinator_address=(coordinator
                                 or os.environ["JAX_COORDINATOR_ADDRESS"]),
            num_processes=(num_processes
                           or int(os.environ.get("JAX_NUM_PROCESSES", "1"))),
            process_id=(process_id
                        or int(os.environ.get("JAX_PROCESS_ID", "0"))))
    else:
        try:
            jax.distributed.initialize()          # TPU autodetection
        except Exception:
            pass                                  # single-process fallback
    return jax.process_index(), jax.process_count()
