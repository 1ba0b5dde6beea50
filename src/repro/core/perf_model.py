"""Analytical performance models for primitive selection (paper Table IV).

The paper's central mechanism is an analytical model that predicts, for a
matrix product ``Z = X @ Y`` with ``X: (m, n)`` at density ``a_x`` and
``Y: (n, d)`` at density ``a_y``, the execution latency of each computation
primitive, so that the runtime Analyzer (Algorithm 7) can map every
kernel/partition to the cheapest primitive.

Two models live here:

* :class:`FPGACostModel` -- Table IV verbatim, parameterized on ``p_sys``.
  Used for the paper-faithful benchmark reproduction (Tables VII/VIII).
* :class:`TPUCostModel` -- the TPU adaptation.  The MXU cannot skip
  individual zero *elements*; the skippable unit is a VMEM *tile*.  The model
  is therefore written over tile densities (fraction of nonzero
  ``tile x tile`` blocks) and roofline terms of TPU v5e, with per-primitive
  efficiency discounts for index-gather bubbles.

Both expose the same interface so the Analyzer / dynasparse_matmul are
model-agnostic:

* ``cycles(primitive, m, n, d, a_x, a_y)`` -> scalar/array cost
* ``select(a_x, a_y)`` -> Primitive (host ints or traced jnp arrays)
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

from repro import hw

ArrayLike = Union[float, np.ndarray, jnp.ndarray]


class Primitive(enum.IntEnum):
    """Computation primitives.  Order matters: used as lax.switch index."""

    SKIP = 0     # alpha_min == 0: the product of an all-zero operand is zero
    GEMM = 1     # dense x dense
    SPDMM = 2    # sparse x dense (skip zeros of the sparser operand)
    SPMM = 3     # sparse x sparse (skip zeros of both operands)


N_PRIMITIVES = len(Primitive)


class Format(enum.IntEnum):
    """Execution formats for a kernel's sparse operand (DESIGN.md section 13).

    The primitive code picks HOW a reduction step computes; the format code
    picks WHAT representation the whole kernel runs in.  DENSE keeps the
    block-tensor path (GEMM/SpDMM/SPMM per task); CSR converts the sparse
    lhs on the fly (D2S) and runs the row-gather SPMM instead.
    """

    DENSE = 0
    CSR = 1


N_FORMATS = len(Format)


@dataclasses.dataclass(frozen=True)
class FPGACostModel:
    """Paper Table IV.  Costs are in accelerator clock cycles.

    GEMM:  p^2 MACs/cycle             -> m*n*d / p^2
    SpDMM: p^2/2 MACs/cycle, skips the sparser operand's zeros
                                      -> 2 * a_min * m*n*d / p^2
    SPMM:  p MACs/cycle, skips both   -> a_x * a_y * m*n*d / p
    """

    p_sys: int = hw.ALVEO_U250.p_sys
    freq_hz: float = hw.ALVEO_U250.freq_hz

    def gemm_cycles(self, m: ArrayLike, n: ArrayLike, d: ArrayLike) -> ArrayLike:
        return (m * n * d) / (self.p_sys ** 2)

    def spdmm_cycles(self, m, n, d, a_x: ArrayLike, a_y: ArrayLike) -> ArrayLike:
        a_min = jnp.minimum(a_x, a_y) if _traced(a_x, a_y) else np.minimum(a_x, a_y)
        return 2.0 * a_min * (m * n * d) / (self.p_sys ** 2)

    def spmm_cycles(self, m, n, d, a_x: ArrayLike, a_y: ArrayLike) -> ArrayLike:
        return a_x * a_y * (m * n * d) / self.p_sys

    def cycles(self, primitive: Primitive, m, n, d, a_x, a_y) -> ArrayLike:
        if primitive == Primitive.SKIP:
            return 0.0 * (a_x + a_y)
        if primitive == Primitive.GEMM:
            return self.gemm_cycles(m, n, d) + 0.0 * (a_x + a_y)
        if primitive == Primitive.SPDMM:
            return self.spdmm_cycles(m, n, d, a_x, a_y)
        if primitive == Primitive.SPMM:
            return self.spmm_cycles(m, n, d, a_x, a_y)
        raise ValueError(f"unknown primitive {primitive}")

    def seconds(self, primitive: Primitive, m, n, d, a_x, a_y) -> ArrayLike:
        return self.cycles(primitive, m, n, d, a_x, a_y) / self.freq_hz

    # -- Algorithm 7 decision rule (closed form of the cost-minimum) ---------
    def select(self, a_x: float, a_y: float) -> Primitive:
        """Host-side K2P decision for one partition pair (Algorithm 7)."""
        a_min, a_max = min(a_x, a_y), max(a_x, a_y)
        if a_min == 0.0:
            return Primitive.SKIP
        if a_min >= 0.5:
            return Primitive.GEMM
        if a_max >= 2.0 / self.p_sys:
            return Primitive.SPDMM
        return Primitive.SPMM

    def select_traced(self, a_x: jnp.ndarray, a_y: jnp.ndarray) -> jnp.ndarray:
        """Vectorized/traceable Algorithm 7: returns int32 Primitive codes."""
        a_min = jnp.minimum(a_x, a_y)
        a_max = jnp.maximum(a_x, a_y)
        out = jnp.where(
            a_min >= 0.5,
            Primitive.GEMM,
            jnp.where(a_max >= 2.0 / self.p_sys, Primitive.SPDMM, Primitive.SPMM),
        )
        return jnp.where(a_min == 0.0, Primitive.SKIP, out).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class TPUCostModel:
    """TPU v5e adaptation of Table IV, over *tile* densities.

    On TPU the primitives are realized as (see ``repro.kernels``):

    * GEMM  -- dense tiled matmul on the MXU.  Cost = roofline
      max(compute, memory) over the full block.
    * SpDMM -- block-sparse x dense: only nonzero ``tile x tile`` blocks of
      the sparser operand are DMA'd/multiplied (scalar-prefetch indexing).
      Compute scales with tile density ``b_min``; a discount factor models
      prefetch bubbles + index bookkeeping.
    * SPMM  -- tile-pair intersection: a (k-)tile is processed only when the
      corresponding tiles of BOTH operands are nonzero.  With independence,
      the surviving fraction is ``b_x * b_y`` (the paper's ``a_X a_Y`` at
      tile granularity); bookkeeping cost is higher.

    ``select`` picks the argmin of predicted seconds, mirroring Algorithm 7
    (SKIP when b_min == 0).  Crossovers land near b_min ~ eff_spdmm and
    b_max ~ eff_spdmm/eff_spmm instead of the FPGA's 1/2 and 2/p; the
    *structure* of the rule is identical.
    """

    spec: hw.TPUSpec = hw.TPU_V5E
    dtype_bytes: int = 2                 # bf16 operands
    eff_gemm: float = 1.00               # MXU efficiency at 128-aligned tiles
    eff_spdmm: float = 0.88              # gather/prefetch bubbles
    eff_spmm: float = 0.72               # intersection bookkeeping
    launch_overhead_s: float = 2e-6      # fixed per-primitive-call overhead
    # -- row-CSR format costs (Fig. 13 runtime-overhead accounting) ----------
    eff_csr: float = 0.45                # row-gather VPU MACs, random-row DMA
    eff_transform: float = 1e-3          # D2S bandwidth derate: the conversion
    #                                      is prefix/gather passes, not
    #                                      streaming copies
    transform_overhead_s: float = 2e-5   # fixed cost of the multi-pass D2S
    csr_fill_slack: float = 3.0          # predicted max row nnz ~= slack *
    #                                      mean (degree-skew headroom)

    def _roofline_seconds(self, flops, bytes_moved, eff) -> ArrayLike:
        t_compute = flops / (self.spec.peak_bf16_flops * eff)
        t_memory = bytes_moved / self.spec.hbm_bandwidth
        mx = jnp.maximum if _traced(flops, bytes_moved) else np.maximum
        return mx(t_compute, t_memory) + self.launch_overhead_s

    def gemm_seconds(self, m, n, d) -> ArrayLike:
        flops = 2.0 * m * n * d
        bytes_moved = (m * n + n * d + m * d) * self.dtype_bytes
        return self._roofline_seconds(flops, bytes_moved, self.eff_gemm)

    def spdmm_seconds(self, m, n, d, b_x, b_y) -> ArrayLike:
        b_min = jnp.minimum(b_x, b_y) if _traced(b_x, b_y) else np.minimum(b_x, b_y)
        flops = 2.0 * b_min * m * n * d
        # sparse operand: only nonzero tiles move; dense operand + output move
        # in full (worst case: every dense tile is touched by some nnz tile).
        bytes_moved = (b_min * m * n + n * d + m * d) * self.dtype_bytes
        return self._roofline_seconds(flops, bytes_moved, self.eff_spdmm)

    def spmm_seconds(self, m, n, d, b_x, b_y) -> ArrayLike:
        flops = 2.0 * b_x * b_y * m * n * d
        bytes_moved = (b_x * m * n + b_y * n * d + m * d) * self.dtype_bytes
        return self._roofline_seconds(flops, bytes_moved, self.eff_spmm)

    def seconds(self, primitive: Primitive, m, n, d, b_x, b_y) -> ArrayLike:
        if primitive == Primitive.SKIP:
            return 0.0 * (b_x + b_y)
        if primitive == Primitive.GEMM:
            return self.gemm_seconds(m, n, d) + 0.0 * (b_x + b_y)
        if primitive == Primitive.SPDMM:
            return self.spdmm_seconds(m, n, d, b_x, b_y)
        if primitive == Primitive.SPMM:
            return self.spmm_seconds(m, n, d, b_x, b_y)
        raise ValueError(f"unknown primitive {primitive}")

    # kept for API parity with FPGACostModel (benchmarks treat cycles=seconds)
    def cycles(self, primitive, m, n, d, b_x, b_y):
        return self.seconds(primitive, m, n, d, b_x, b_y)

    def select(self, b_x: float, b_y: float, m=128, n=128, d=128) -> Primitive:
        if min(b_x, b_y) == 0.0:
            return Primitive.SKIP
        costs = {
            Primitive.GEMM: float(self.gemm_seconds(m, n, d)),
            Primitive.SPDMM: float(self.spdmm_seconds(m, n, d, b_x, b_y)),
            Primitive.SPMM: float(self.spmm_seconds(m, n, d, b_x, b_y)),
        }
        return min(costs, key=costs.get)

    def select_traced(self, b_x, b_y, m=128, n=128, d=128) -> jnp.ndarray:
        shape = jnp.broadcast_shapes(jnp.shape(b_x), jnp.shape(b_y))
        costs = jnp.stack(
            [
                jnp.broadcast_to(self.gemm_seconds(m, n, d), shape),
                jnp.broadcast_to(self.spdmm_seconds(m, n, d, b_x, b_y), shape),
                jnp.broadcast_to(self.spmm_seconds(m, n, d, b_x, b_y), shape),
            ]
        )
        best = jnp.argmin(costs, axis=0).astype(jnp.int32) + 1  # offset: GEMM=1
        return jnp.where(jnp.minimum(b_x, b_y) == 0.0, Primitive.SKIP, best)

    # -- format selection (row-CSR vs the block path) ------------------------

    def csr_spmm_seconds(self, m, n, d, rmax) -> ArrayLike:
        """Row-gather SPMM over the padded ELL view: every row issues
        ``rmax`` slot MACs across ``d`` output lanes; bytes are dominated by
        the gathered rhs rows (one (d,)-row DMA per slot)."""
        flops = 2.0 * m * rmax * d
        bytes_moved = (m * rmax * (4 + self.dtype_bytes)       # cols + vals
                       + m * rmax * d * self.dtype_bytes       # gathered rows
                       + m * d * self.dtype_bytes)             # output
        return self._roofline_seconds(flops, bytes_moved, self.eff_csr)

    def transform_seconds(self, m, n, rmax) -> ArrayLike:
        """Dense -> row-CSR conversion (D2S): reads the dense (m, n) operand
        and writes the padded (m, rmax) ELL view -- int32 column ids plus
        values, so the write side scales with the ``rmax`` row budget, NOT
        with n (``dense_to_ell`` never materialises an (m, n) compacted
        buffer) -- at conversion efficiency (prefix networks and
        rank-select gathers, far off streaming bandwidth), plus a fixed
        multi-pass overhead."""
        bytes_moved = (m * n * self.dtype_bytes                # dense read
                       + m * rmax * (4 + self.dtype_bytes))    # cols + vals
        return (bytes_moved / (self.spec.hbm_bandwidth * self.eff_transform)
                + self.transform_overhead_s)

    def select_format_traced(self, m, n, d, block_dims, nnz, occupied_steps,
                             rmax) -> jnp.ndarray:
        """Fig. 13 accounting, traceable: CSR wins only when conversion PLUS
        gather execution beat the block path's occupied reduction steps, AND
        the predicted max row fill fits ``rmax`` (lossless guard).

        ``occupied_steps`` is the number of (i, j, k) tasks whose operand
        blocks are both nonzero -- the steps the block path cannot SKIP; each
        is charged one block-GEMM (an upper bound that SpDMM/SPMM tighten,
        but launch overhead dominates at these block sizes).  The transform
        cost is charged in full to EVERY kernel even when the executor will
        reuse one conversion, so a decision is a pure function of the
        kernel's own densities: a kernel run alone through
        ``dynasparse_matmul`` reaches the same decision and really does
        convert for itself.
        """
        bm, bk, bn_ = block_dims
        block_s = occupied_steps * self.gemm_seconds(bm, bk, bn_)
        csr_s = self.transform_seconds(m, n, rmax) + self.csr_spmm_seconds(
            m, n, d, rmax)
        fits = nnz * self.csr_fill_slack <= rmax * m
        return jnp.where((csr_s < block_s) & fits,
                         Format.CSR, Format.DENSE).astype(jnp.int32)


@dataclasses.dataclass
class CostCalibration:
    """EWMA calibration from Analyzer cost units to measured wall seconds.

    The Table-IV models predict *relative* cost (cycles on the FPGA model,
    idealized roofline seconds on the TPU model); dispatch walls on a real
    host include trace/launch/padding overheads the models deliberately
    ignore.  The serving admission controller (DESIGN.md section 15) needs
    absolute seconds to compare a predicted completion against a deadline,
    so it folds every observed ``(predicted cost, measured wall)`` pair
    into an EWMA of seconds-per-cost-unit and converts per-request
    Analyzer costs (``GraphServeEngine.request_cost``) through it.

    ``seconds`` returns ``fallback`` until the first observation (cold
    start belongs to the caller -- the scheduler already tracks per-bucket
    EWMA walls for exactly that).  Zero-cost observations are skipped:
    an all-SKIP wave's wall is launch overhead, not a unit rate.
    """

    alpha: float = 0.25
    seconds_per_unit: Optional[float] = None

    def observe(self, cost_units: float, wall_seconds: float) -> None:
        if cost_units <= 0.0 or wall_seconds <= 0.0:
            return
        rate = float(wall_seconds) / float(cost_units)
        if self.seconds_per_unit is None:
            self.seconds_per_unit = rate
        else:
            self.seconds_per_unit += self.alpha * (rate - self.seconds_per_unit)

    def seconds(self, cost_units: float, fallback: float = 0.0) -> float:
        if self.seconds_per_unit is None:
            return fallback
        return float(cost_units) * self.seconds_per_unit


def predict_output_density(a_x: ArrayLike, a_y: ArrayLike, n: ArrayLike) -> ArrayLike:
    """Expected density of Z = X @ Y under independent Bernoulli nonzeros.

    P(z_ij != 0) = 1 - (1 - a_x * a_y)^n.  Used by the Analyzer to seed the
    density estimate of layer l+1 before the profiler confirms it (the paper
    overlaps K2P of layer l+1 with execution of layer l).
    """
    one = 1.0
    if _traced(a_x, a_y):
        return one - (one - a_x * a_y) ** n
    return one - np.power(one - np.asarray(a_x) * np.asarray(a_y), n)


def _traced(*xs) -> bool:
    return any(isinstance(x, jnp.ndarray) and not isinstance(x, np.ndarray) for x in xs)
