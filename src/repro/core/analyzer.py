"""Runtime Analyzer: kernel-to-primitive mapping for every strategy.

For a computation task Z_ij = sum_t X_it @ Y_tj, the Analyzer fetches the
densities of every partition pair and picks the target primitive (and buffer
assignment, which on TPU becomes "which operand is the gathered/sparse one").

:func:`plan_codes` is THE planner: it produces the (I, J, K) primitive-code
grid for all four mapping strategies (Section VIII-B) -- ``dynamic``
(Algorithm 7, the contribution), ``s1`` (HyGCN/BoostGCN), ``s2`` (AWB-GCN),
``gemm`` (dense lower bound) -- and is pure jnp, so the same code runs on the
host (soft-processor role) and traced inside the jit-compiled unified
executor (``core.dynasparse.dynasparse_matmul``).  See DESIGN.md section 1.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.ir import KernelType
from repro.core.perf_model import (FPGACostModel, Primitive, TPUCostModel,
                                   _traced)

CostModel = object  # FPGACostModel | TPUCostModel (duck-typed)

STRATEGIES = ("dynamic", "s1", "s2", "gemm")


def static_primitive(strategy: str,
                     kernel_type: Optional[KernelType]) -> Primitive:
    """The fixed primitive of a static strategy (s1/s2/gemm)."""
    if strategy == "s1":
        if kernel_type is None:
            raise ValueError("strategy 's1' maps by kernel type; pass one")
        return (Primitive.SPDMM if kernel_type == KernelType.AGGREGATE
                else Primitive.GEMM)
    if strategy == "s2":
        return Primitive.SPDMM
    if strategy == "gemm":
        return Primitive.GEMM
    raise ValueError(f"unknown strategy {strategy!r}")


def plan_codes(
    strategy: str,
    dens_x: jnp.ndarray,          # (I, K) block densities of X
    dens_y: jnp.ndarray,          # (K, J) block densities of Y
    model: CostModel,
    *,
    kernel_type: Optional[KernelType] = None,
) -> jnp.ndarray:
    """K2P decision grid: (I, K) x (K, J) -> (I, J, K) int32 Primitive codes.

    The single source of truth for every strategy.  ``strategy`` and
    ``kernel_type`` are trace-static; the densities may be host numpy or
    traced jnp -- under jit this is the paper's Analyzer fused into the
    executor, on the host it is the soft processor's decision loop
    (vectorized).

    Shape conventions: ``dens_x``/``dens_y`` are the operand block-density
    grids AT THE KERNEL'S TASK GRANULARITY -- (I, K) for X partitioned
    (bm, bk) and (K, J) for Y partitioned (bk, bn), normalized to the
    unpadded elements per block (``profiler.density_from_counts``).
    Feature-matrix profiles are stored at (N2, N2) repo-wide; callers
    pooling them for an Aggregate's (N1, N2) fiber view use
    ``profiler.BlockProfile.pool_rows`` (exact) or the simulator's
    ``runtime._pool_rows`` (mean-pool).  Decision (i, j, k) maps the
    reduction step X[i,k] @ Y[k,j]; ``strategy``: ``dynamic`` = Algorithm 7
    via ``model.select_traced``, ``s1`` = SpDMM for Aggregate / GEMM for
    Update (needs ``kernel_type``), ``s2`` = all SpDMM, ``gemm`` = all
    dense.  Static strategies never emit SKIP.
    """
    I, K = dens_x.shape[0], dens_x.shape[1]
    J = dens_y.shape[1]
    if strategy != "dynamic":
        # static mappings ignore the densities: constant grid, no broadcast
        # (and no device work on the host path).
        prim = static_primitive(strategy, kernel_type)
        xp = jnp if _traced(dens_x, dens_y) else np
        return xp.full((I, J, K), int(prim), xp.int32)
    ax = jnp.asarray(dens_x)[:, None, :]                    # (I, 1, K)
    ay = jnp.swapaxes(jnp.asarray(dens_y), 0, 1)[None]      # (1, J, K)
    ax, ay = jnp.broadcast_arrays(ax, ay)
    return model.select_traced(ax, ay)


def plan_codes_from_profiles(
    strategy: str,
    prof_x,                       # profiler.BlockProfile at (bm, bk) blocks
    prof_y,                       # profiler.BlockProfile at (bk, bn) blocks
    model: CostModel,
    *,
    kernel_type: Optional[KernelType] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """K2P planning from PROPAGATED writeback profiles, not operands.

    This is the layer-overlap entry point (paper Section V-B2): the fused
    whole-model executor hands in each operand's ``profiler.BlockProfile``
    -- either measured once for a graph input, or pooled from the producing
    kernel's ``out_counts`` writeback profile -- already at this kernel's
    consumer granularity.  Because the plan depends only on the producer's
    profile (emitted at writeback) and never on the materialized operand,
    XLA is free to schedule layer l+1's planning concurrently with layer
    l's task loop, which is the soft-processor/accelerator overlap of the
    paper realized inside one traced program.

    Returns ``(codes, dens_x, dens_y)``: the (I, J, K) primitive grid plus
    the densities it was planned from (the executor's side-output /
    bookkeeping contract, bitwise equal to in-trace re-profiling).
    """
    dens_x = prof_x.densities()
    dens_y = prof_y.densities()
    codes = plan_codes(strategy, dens_x, dens_y, model,
                       kernel_type=kernel_type)
    return codes, dens_x, dens_y


def delta_replan_mask(
    strategy: str,
    old_dens_x: np.ndarray,       # (I, K) lhs block densities before delta
    new_dens_x: np.ndarray,       # (I, K) lhs block densities after delta
    dens_y: np.ndarray,           # (K, J) rhs block densities (unchanged)
    model: CostModel,
    *,
    touched: Optional[np.ndarray] = None,   # (I, K) bool: cells to examine
    kernel_type: Optional[KernelType] = None,
) -> np.ndarray:
    """Which lhs cells a streaming graph delta forces to REPLAN.

    Returns the (I, K) bool mask of lhs blocks whose K2P decision against
    at least one rhs block CHANGED between the old and new densities --
    i.e. the density moved across a primitive boundary (SKIP/GEMM/SpDMM/
    SpMM).  Exactness argument: ``plan_codes`` is a pure function of the
    density pair, so a cell whose density did not change (or changed
    without crossing a boundary) keeps its exact old plan; re-``select``-ing
    ONLY the ``touched`` cells (the incremental profile patch's touched
    mask, ``data.sampling.AdjacencyBlockProfile.apply_delta``) therefore
    reproduces the diff of two full replans, in O(touched * J) instead of
    O(I * J * K) work.  Static strategies never consult densities, so their
    mask is empty (their plans cannot move).
    """
    old = np.asarray(old_dens_x)
    new = np.asarray(new_dens_x)
    if touched is None:
        touched = old != new
    out = np.zeros(old.shape, bool)
    if strategy != "dynamic" or not np.any(touched):
        return out
    ti, tk = np.nonzero(touched)
    ay = np.asarray(dens_y)[tk, :]                       # (t, J)
    c_old = np.asarray(model.select_traced(old[ti, tk][:, None], ay))
    c_new = np.asarray(model.select_traced(new[ti, tk][:, None], ay))
    out[ti, tk] = np.any(c_old != c_new, axis=1)
    return out


def plan_format(
    strategy: str,
    dens_x: jnp.ndarray,          # (I, K) block densities of X
    dens_y: jnp.ndarray,          # (K, J) block densities of Y
    lhs_shape: Tuple[int, int],   # unpadded (m, k) of X
    rhs_cols: int,                # d: output columns
    block_dims: Tuple[int, int, int],
    model: CostModel,
    *,
    kernel_type: Optional[KernelType] = None,
    rmax: int = 0,
) -> Optional[jnp.ndarray]:
    """The format half of the (primitive, format) K2P decision.

    Returns ``None`` when the kernel is STATICALLY dense -- static strategies
    (their contract is a fixed mapping), non-Aggregate kernels (the sparse
    row format only models a graph-structured lhs), ``rmax <= 0``, or a cost
    model without format costs (``FPGACostModel``: the paper's FPGA has
    element-granular primitives, so block-vs-row is moot) -- in which case
    the caller keeps the block path with ZERO added trace.  Otherwise a
    traced () int32 ``Format`` code from the same density grids the
    primitive plan used, so identical profiles give identical decisions in
    the single-inference and wave programs (the bitwise-parity invariant).

    The model sees Fig. 13's full accounting: the lhs nonzero count
    (reconstructed exactly from the ragged-aware block densities), the
    number of reduction steps the block path cannot SKIP, and the
    transformation cost of converting the lhs on the fly.
    """
    if rmax <= 0 or strategy != "dynamic":
        return None
    if kernel_type != KernelType.AGGREGATE:
        return None
    if not hasattr(model, "select_format_traced"):
        return None
    m, k = lhs_shape
    bm, bk, _ = block_dims
    I, K = dens_x.shape
    # exact unpadded element count per block (ragged edges included)
    rows = np.clip(m - bm * np.arange(I), 0, bm)
    cols = np.clip(k - bk * np.arange(K), 0, bk)
    elems = np.outer(rows, cols).astype(np.float32)
    nnz = jnp.sum(jnp.asarray(dens_x) * elems)
    ax = jnp.asarray(dens_x)[:, None, :]                    # (I, 1, K)
    ay = jnp.swapaxes(jnp.asarray(dens_y), 0, 1)[None]      # (1, J, K)
    occupied = jnp.sum((ax > 0) & (ay > 0))
    return model.select_format_traced(m, k, rhs_cols, block_dims, nnz,
                                      occupied, rmax)


def task_costs(
    codes: jnp.ndarray,           # (I, J, K) int32 Primitive codes
    dens_x: jnp.ndarray,          # (I, K)
    dens_y: jnp.ndarray,          # (K, J)
    block_dims: Tuple[int, int, int],
    model: CostModel,
) -> jnp.ndarray:
    """Per-task predicted cost (I, J): Table IV cost summed over the K
    reduction steps under each step's selected primitive.  Feeds Algorithm 8
    scheduling and Fig. 13 overhead.  Backend-matching: pure numpy on host
    inputs (the engine's bookkeeping path), jnp under trace."""
    bm, bk, bn = block_dims
    xp = jnp if _traced(codes, dens_x, dens_y) else np
    ax = xp.asarray(dens_x, dtype=xp.float64 if xp is np else jnp.float32)
    ay = xp.asarray(dens_y, dtype=ax.dtype)
    ax = ax[:, None, :]                                     # (I, 1, K)
    ay = xp.swapaxes(ay, 0, 1)[None]                        # (1, J, K)
    ax, ay = xp.broadcast_arrays(ax, ay)
    step = xp.where(
        codes == Primitive.GEMM,
        model.cycles(Primitive.GEMM, bm, bk, bn, ax, ay),
        xp.where(
            codes == Primitive.SPDMM,
            model.cycles(Primitive.SPDMM, bm, bk, bn, ax, ay),
            xp.where(
                codes == Primitive.SPMM,
                model.cycles(Primitive.SPMM, bm, bk, bn, ax, ay),
                0.0)))
    return step.sum(axis=2)


def task_costs_host(
    codes: np.ndarray,
    dens_x: np.ndarray,
    dens_y: np.ndarray,
    block_dims: Tuple[int, int, int],
    model: CostModel,
    *,
    chunk_elems: float = 2e6,
) -> np.ndarray:
    """Chunked :func:`task_costs` for host grids (bounds broadcast temps)."""
    I, J, K = codes.shape
    costs = np.empty((I, J), np.float64)
    chunk = max(1, int(chunk_elems / max(J * K, 1)))
    for i0 in range(0, I, chunk):
        i1 = min(i0 + chunk, I)
        costs[i0:i1] = task_costs(codes[i0:i1], dens_x[i0:i1], dens_y,
                                  block_dims, model)
    return costs


def plan_kernel_host(
    strategy: str,
    dens_x: np.ndarray,
    dens_y: np.ndarray,
    block_dims: Tuple[int, int, int],
    model: CostModel,
    *,
    kernel_type: Optional[KernelType] = None,
    chunk_elems: float = 2e6,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side planning for one kernel: (codes (I,J,K), costs (I,J)) np.

    Chunks over output rows: NELL-sized decision grids (I*J*K ~ 1e7+) would
    otherwise materialize multi-GB broadcast temporaries."""
    I, K = dens_x.shape
    J = dens_y.shape[1]
    codes = np.empty((I, J, K), np.int32)
    costs = np.empty((I, J), np.float64)
    chunk = max(1, int(chunk_elems / max(J * K, 1)))
    for i0 in range(0, I, chunk):
        i1 = min(i0 + chunk, I)
        c = np.asarray(plan_codes(strategy, dens_x[i0:i1], dens_y, model,
                                  kernel_type=kernel_type))
        codes[i0:i1] = c
        costs[i0:i1] = task_costs(c, dens_x[i0:i1], dens_y, block_dims, model)
    return codes, costs


@dataclasses.dataclass
class TaskPlan:
    """K2P decision for one task (one output partition Z_ij)."""

    i: int
    k: int
    primitives: np.ndarray        # (K,) Primitive codes per reduction step
    sparse_is_lhs: np.ndarray     # (K,) bool: which operand goes to BufferU
    est_cost: float               # predicted cycles/seconds for the task

    @property
    def skipped(self) -> int:
        return int(np.sum(self.primitives == Primitive.SKIP))


def plan_task(
    model: CostModel,
    dens_x_row: np.ndarray,     # (K,) densities of X_i,1..K
    dens_y_col: np.ndarray,     # (K,) densities of Y_1..K,j
    dims: Tuple[int, int, int],
    i: int = 0,
    k: int = 0,
) -> TaskPlan:
    """Algorithm 7 over all reduction steps of one task (host-side)."""
    m, n, d = dims
    K = len(dens_x_row)
    prims = np.empty((K,), np.int32)
    sparse_lhs = np.zeros((K,), bool)
    cost = 0.0
    for t in range(K):
        ax, ay = float(dens_x_row[t]), float(dens_y_col[t])
        p = model.select(ax, ay)
        prims[t] = p
        # Alg. 7: the sparser operand goes to BufferU (is the gathered one)
        sparse_lhs[t] = ax <= ay
        cost += float(model.cycles(p, m, n, d, ax, ay))
    return TaskPlan(i=i, k=k, primitives=prims, sparse_is_lhs=sparse_lhs,
                    est_cost=cost)


def plan_kernel(
    model: CostModel,
    dens_x: np.ndarray,   # (I, K) block densities of X
    dens_y: np.ndarray,   # (K, J) block densities of Y
    block_dims: Tuple[int, int, int],
) -> List[TaskPlan]:
    """K2P for every task of a kernel.  O(I*J*K) scalars -- the paper's
    'small overhead compared with the computation complexity of a task'."""
    I, K = dens_x.shape
    K2, J = dens_y.shape
    assert K == K2, (dens_x.shape, dens_y.shape)
    return [
        plan_task(model, dens_x[i], dens_y[:, j], block_dims, i=i, k=j)
        for i in range(I)
        for j in range(J)
    ]


def primitive_histogram(plans: List[TaskPlan]) -> np.ndarray:
    """Counts of [SKIP, GEMM, SPDMM, SPMM] across all reduction steps."""
    hist = np.zeros((4,), np.int64)
    for p in plans:
        for v in p.primitives:
            hist[int(v)] += 1
    return hist
