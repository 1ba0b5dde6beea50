"""Production mesh construction + recommended XLA flags.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its first
jax import, and nothing here may run before that.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Latency-hiding / async-collective flags for REAL TPU runs (compute/comm
# overlap).  The CPU dry-run ignores them; launch/train.py exports them.
TPU_PERF_FLAGS = " ".join([
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_all_gather=true",
    "--xla_tpu_enable_async_collective_permute=true",
    "--xla_enable_async_all_reduce=true",
    "--xla_tpu_spmd_threshold_for_allgather_cse=10000",
])


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism (gradient reduction crosses DCN, everything else
    stays inside a pod's ICI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: int = 8, model: int = 4):
    """Small host-device mesh for unit tests (needs
    XLA_FLAGS=--xla_force_host_platform_device_count=<n> in the test env)."""
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
