"""Sharding rules: FSDP + TP by construction, divisibility-guarded.

Parameters: every rank>=2 leaf shards its LAST dim over ``model`` (tensor
parallel: ffn hidden, attention heads-flattened, vocab-transposed) and its
SECOND-TO-LAST dim over ``data`` (FSDP) -- whenever divisible.  Stacked
(scan-over-layers) leaves keep their leading layer dim replicated.  The
optimizer state mirrors params leaf-for-leaf, so this single rule gives
ZeRO-3-style full parameter+state sharding over the (data x model) grid;
gradients arrive reduce-scattered by GSPMD.

Caches: batch dim over (pod, data); the largest remaining dim divisible by
the model-axis size shards over ``model`` -- that resolves to heads for
divisible GQA, the SEQUENCE for 8-kv-head caches and MLA latents (sequence-
sharded KV), d_inner for Mamba states, and head_dim for xLSTM matrix
memories.  Batch=1 long-context falls back to model-axis-only sharding.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["NamedSharding", "P", "batch_axes", "param_spec",
           "param_shardings", "cache_spec", "cache_shardings",
           "batch_spec", "batch_shardings", "replicated", "describe",
           "CORES_AXIS", "cores_mesh", "wave_spec", "wave_shardings",
           "partition_devices", "partition_mesh", "abstract_cores_mesh"]

# the serving mesh axis: each device along it plays one of the paper's
# Computation Cores, executing its own slice of an admission wave
# (DESIGN.md section 12).
CORES_AXIS = "cores"


def cores_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D serving mesh over ``CORES_AXIS``.

    Uses the first ``n_devices`` local devices (all of them by default).
    A 1-device mesh is valid and makes the sharded wave dispatch collapse
    to the single-lane program (bitwise-identical outputs, tested).
    """
    devs = jax.devices()
    if n_devices is not None:
        if not 0 < n_devices <= len(devs):
            raise ValueError(
                f"cores_mesh({n_devices}) with {len(devs)} devices visible")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (CORES_AXIS,))


def partition_devices(devices: Sequence, group_sizes: Sequence[int]
                      ) -> List[list]:
    """Split ``devices`` into contiguous disjoint groups of ``group_sizes``.

    The pure partition rule behind :func:`partition_mesh` (property-tested
    on plain lists in ``tests/test_submesh_partition.py``): every device
    lands in exactly ONE group, groups keep device order, and the sizes
    must form an exact cover -- every size positive, summing to
    ``len(devices)``.  Anything else raises ``ValueError`` (a dispatch
    layer must never silently drop or double-book a device).
    """
    sizes = [int(s) for s in group_sizes]
    if not sizes:
        raise ValueError("partition into zero groups")
    bad = [s for s in sizes if s < 1]
    if bad:
        raise ValueError(f"group sizes must be >= 1, got {sizes}")
    if sum(sizes) != len(devices):
        raise ValueError(
            f"group sizes {sizes} sum to {sum(sizes)}, not the "
            f"{len(devices)} devices to partition")
    out, at = [], 0
    for s in sizes:
        out.append(list(devices[at: at + s]))
        at += s
    return out


def partition_mesh(mesh: Mesh, group_sizes: Sequence[int]) -> List[Mesh]:
    """Partition a 1-D ``cores`` mesh into disjoint per-lane submeshes.

    Every device of ``mesh`` lands in exactly one group (sizes must be
    positive and sum to the device count -- :func:`partition_devices`);
    each group becomes its own 1-D ``cores`` mesh, so dispatch lanes can
    execute waves on genuinely disjoint hardware (DESIGN.md section 14).
    Submesh programs are traced against :func:`abstract_cores_mesh`, so
    equal-size groups share ONE compiled program -- the trace bound is per
    group *size*, not per device identity.
    """
    if len(mesh.axis_names) != 1 or mesh.axis_names[0] != CORES_AXIS:
        raise ValueError(
            f"partition_mesh needs a 1-D {CORES_AXIS!r} mesh, got "
            f"{mesh.axis_names}")
    groups = partition_devices(list(mesh.devices.flat), group_sizes)
    return [Mesh(np.asarray(g), (CORES_AXIS,)) for g in groups]


def abstract_cores_mesh(n_devices: int) -> AbstractMesh:
    """Device-free 1-D ``cores`` mesh of ``n_devices``: the trace key for
    submesh dispatch.  A ``shard_map`` program built over the abstract
    mesh binds to CONCRETE devices at call time from its inputs'
    shardings, so one jitted program serves every disjoint device group of
    the same size (one trace per (bucket, group size))."""
    if n_devices < 1:
        raise ValueError(f"abstract_cores_mesh({n_devices})")
    return AbstractMesh((int(n_devices),), (CORES_AXIS,))


def wave_spec() -> P:
    """Spec for stacked per-request wave tensors ``(B, ...)``: the request
    axis shards over ``CORES_AXIS``, everything per-request stays local."""
    return P(CORES_AXIS)


def wave_shardings(mesh: Mesh, batched_abstract: Any) -> Any:
    """NamedShardings placing every stacked wave leaf on the cores mesh."""
    return jax.tree.map(
        lambda _: NamedSharding(mesh, wave_spec()), batched_abstract)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axsize(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


# Megatron convention: down/output projections are ROW-parallel (their
# contraction dim -- the previous op's model-sharded output -- shards over
# `model`); everything else is column-parallel.  Getting this wrong makes
# GSPMD fully replicate the weight to resolve the contraction mismatch
# (a 6 GiB/chip f32 copy of grok's we2, caught by the first sweep).
ROW_PARALLEL_NAMES = ("w2", "wo", "we2", "out_proj", "down", "dt_proj")


def param_spec(mesh: Mesh, shape: Tuple[int, ...],
               row_parallel: bool = False) -> P:
    if len(shape) < 2:
        return P()
    spec = [None] * len(shape)
    model_n = _axsize(mesh, "model") if "model" in mesh.axis_names else 0
    data_n = _axsize(mesh, "data") if "data" in mesh.axis_names else 0
    mdim, ddim = (-2, -1) if row_parallel else (-1, -2)
    if model_n > 1 and shape[mdim] % model_n == 0:
        spec[mdim] = "model"
    if data_n > 1 and shape[ddim] % data_n == 0:
        spec[ddim] = "data"
    elif model_n > 1 and spec[mdim] is None and shape[ddim] % model_n == 0:
        spec[ddim] = "model"
    return P(*spec)


def _is_row_parallel(path) -> bool:
    for k in reversed(path):
        name = getattr(k, "key", None) or getattr(k, "name", "")
        if isinstance(name, str) and name:
            if name in ("q", "s"):      # Quantized state wrapper fields
                continue
            return name in ROW_PARALLEL_NAMES
    return False


def _is_expert(path) -> bool:
    for k in reversed(path):
        name = getattr(k, "key", None) or getattr(k, "name", "")
        if isinstance(name, str) and name:
            if name in ("q", "s"):
                continue
            return name in ("we1", "we2", "we3")
    return False


def expert_param_spec(mesh: Mesh, shape, row_parallel: bool) -> P:
    """EP: experts over `data`, TP over `model` inside each expert -- no
    FSDP gather of expert weights; dispatch becomes a data-axis all-to-all
    of token activations (the collective-bound hillclimb)."""
    spec = [None] * len(shape)
    data_n = _axsize(mesh, "data") if "data" in mesh.axis_names else 0
    model_n = _axsize(mesh, "model") if "model" in mesh.axis_names else 0
    edim = len(shape) - 3
    if data_n > 1 and shape[edim] % data_n == 0:
        spec[edim] = "data"
    mdim = -2 if row_parallel else -1
    if model_n > 1 and shape[mdim] % model_n == 0:
        spec[mdim] = "model"
    return P(*spec)


def param_shardings(mesh: Mesh, params_abstract, *,
                    ep_experts: bool = False) -> Any:
    def leaf(path, l):
        row = _is_row_parallel(path)
        if ep_experts and _is_expert(path) and l.ndim >= 3:
            return NamedSharding(mesh, expert_param_spec(mesh, l.shape, row))
        return NamedSharding(mesh, param_spec(mesh, l.shape,
                                              row_parallel=row))
    return jax.tree_util.tree_map_with_path(leaf, params_abstract)


def cache_spec(mesh: Mesh, shape: Tuple[int, ...], batch: int) -> P:
    spec = [None] * len(shape)
    ba = batch_axes(mesh)
    bn = _axsize(mesh, ba) if ba else 0
    model_n = _axsize(mesh, "model") if "model" in mesh.axis_names else 0
    # find the batch dim (first dim equal to the global batch, skipping a
    # possible leading stacked-layer dim)
    bdim = None
    for d, sz in enumerate(shape):
        if sz == batch and (d <= 1):
            bdim = d
            break
    if bdim is not None and bn > 1 and batch % bn == 0:
        spec[bdim] = ba if len(ba) > 1 else ba[0]
    if model_n > 1:
        # prefer the MINOR-most divisible dim (head_dim / MLA latent /
        # d_inner): decode writes one token per step with
        # dynamic_update_slice along seq, and a seq-sharded cache forces
        # GSPMD to gather the whole cache per step (26 GiB/chip on grok --
        # caught by the first sweep).  Contractions over the sharded minor
        # dim psum instead, which is tiny at decode.
        cands = [d for d, sz in enumerate(shape)
                 if spec[d] is None and d != 0 and sz % model_n == 0]
        if cands:
            spec[cands[-1]] = "model"
    return P(*spec)


def cache_shardings(mesh: Mesh, caches_abstract, batch: int) -> Any:
    return jax.tree.map(
        lambda l: NamedSharding(mesh, cache_spec(mesh, l.shape, batch)),
        caches_abstract)


def batch_spec(mesh: Mesh, shape: Tuple[int, ...], batch: int) -> P:
    if not shape or shape[0] != batch:
        return P()
    ba = batch_axes(mesh)
    bn = _axsize(mesh, ba)
    if bn > 1 and batch % bn == 0:
        return P(ba if len(ba) > 1 else ba[0])
    return P()


def batch_shardings(mesh: Mesh, batch_abstract, batch: int) -> Any:
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(mesh, l.shape, batch)),
        batch_abstract)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def describe(shardings, max_lines: int = 0) -> str:
    """Debug/report helper: path -> spec."""
    lines = []
    for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]:
        name = jax.tree_util.keystr(path)
        lines.append(f"{name}: {s.spec}")
    if max_lines:
        lines = lines[:max_lines]
    return "\n".join(lines)
