"""Host-runtime engine: the soft processor's runtime system (Section VI).

Two entry points:

* :class:`FusedModelExecutor` -- executes a compiled GNN (IR from
  ``core.compiler``) with REAL numerics as one jit-compiled program per
  inference (``run``) or per wave of stacked inferences (``run_batch``).
  Every kernel is planned (``analyzer`` -- Algorithm 7 or a static
  strategy) and executed inside that program through
  ``core.dynasparse``; the host plays the MicroBlaze's role for
  bookkeeping only (Alg. 8 makespan, histograms, reports).  See DESIGN.md
  sections 1 and 9.

* :func:`simulate_inference` -- pure cost-model execution (no numerics):
  given per-tensor density statistics it produces the predicted latency of a
  strategy on the paper's FPGA (or the TPU model).  This is how the
  paper-table benchmarks evaluate graphs whose dense materialization would
  not fit this container (NELL/Reddit), mirroring how the paper's own
  latency derives from its Table IV model + measured densities + Alg. 8
  load balance.

Strategies (Section VIII-B; the K2P rules live in ``analyzer.plan_codes``):
  dynamic -- Algorithm 7 (the contribution)
  s1      -- HyGCN/BoostGCN: Aggregate->SpDMM, Update->GEMM
  s2      -- AWB-GCN: everything->SpDMM
  gemm    -- everything dense (CPU/GPU-library-style lower bound)
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections.abc import Mapping
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro import spans
from repro.core import analyzer, profiler, scheduler
from repro.core import formats as _formats
from repro.distributed import sharding as dist_sharding
from repro.core.compiler import CompiledModel
from repro.core.dynasparse import (DynasparseResult, attention_adjacency,
                                   dynasparse_matmul, ell_when, head_combine)
from repro.core.ir import Activation, AggOp, KernelIR, KernelType
from repro.core.perf_model import FPGACostModel, Format
from repro.core.profiler import SparsityStats

# instructions the soft processor spends per K2P decision (Alg. 7 is a few
# compares + buffer assignment); 500 MIPS MicroBlaze (Section VII).
_K2P_INSTRUCTIONS = 32
_SOFT_PROC_IPS = 500e6


@dataclasses.dataclass
class KernelReport:
    name: str
    num_tasks: int
    histogram: np.ndarray            # [SKIP, GEMM, SPDMM, SPMM] step counts
    makespan_cycles: float           # predicted, after Alg. 8 scheduling
    utilization: float
    k2p_seconds: float               # modeled soft-processor time
    # measured host Analyzer-bookkeeping wall time (cost prediction + Alg. 8
    # scheduling + histogram).  The K2P decisions themselves execute inside
    # the jitted executable; their soft-processor cost is k2p_seconds.
    k2p_wall_seconds: float = 0.0
    dens_x: Optional[np.ndarray] = None   # (I, K) profiled lhs densities
    dens_y: Optional[np.ndarray] = None   # (K, J) profiled rhs densities


@dataclasses.dataclass
class InferenceReport:
    # the per-kernel rows, or a function that builds them on the first read
    # of ``kernels`` (the fused program defers its modelled bookkeeping
    # until a caller asks for it)
    rows: Union[List[KernelReport], Callable[[], List[KernelReport]]]
    strategy: str
    # the program's wall time, set by the executor (kernels have no wall
    # time of their own inside one XLA program); None when simulated
    fused_wall_seconds: Optional[float] = None
    # per-wave plumbing (set on the batched serving path): the dispatched
    # wave's batch width, and -- filled in by the admission layer, which is
    # the only place that knows real from dummy -- how many of those slots
    # carried real requests.  The continuous scheduler's EWMA wave-wall
    # estimator and the serving benchmarks read these.
    wave_slots: Optional[int] = None
    wave_real: Optional[int] = None
    # lane count the wave was dispatched over (1 when unsharded): the size
    # of the ``cores`` mesh axis run_batch sharded the request scan across.
    wave_lanes: int = 1
    # (n_kernels, 4) int32 primitive counts [SKIP, GEMM, SPDMM, SPMM] that
    # the fused program counted on the device; ``histogram`` sums them
    # without building the rows.
    counts: Optional[np.ndarray] = None

    @property
    def kernels(self) -> List[KernelReport]:
        if callable(self.rows):
            self.rows = self.rows()
        return self.rows

    @property
    def total_cycles(self) -> float:
        return float(sum(k.makespan_cycles for k in self.kernels))

    def total_seconds(self, freq_hz: float) -> float:
        return self.total_cycles / freq_hz

    @property
    def k2p_seconds(self) -> float:
        return float(sum(k.k2p_seconds for k in self.kernels))

    def k2p_exposed_seconds(self, freq_hz: float) -> float:
        """Modeled K2P time left on the critical path under layer overlap.

        The paper's runtime plans kernel l+1 on the soft processor while the
        accelerator executes kernel l (Section V-B2), so only the first
        kernel's planning plus any per-kernel planning time EXCEEDING the
        previous kernel's execution is exposed.  The executor realizes
        exactly this dependence structure (plan l+1 from l's writeback
        profile), so this is its modeled K2P overhead; ``k2p_seconds`` is
        the non-overlapped sum.
        """
        ks = self.kernels
        if not ks:
            return 0.0
        exposed = ks[0].k2p_seconds
        for prev, cur in zip(ks, ks[1:]):
            exposed += max(0.0, cur.k2p_seconds
                           - prev.makespan_cycles / freq_hz)
        return exposed

    @property
    def k2p_wall_seconds(self) -> float:
        return float(sum(k.k2p_wall_seconds for k in self.kernels))

    @property
    def wall_seconds(self) -> float:
        return self.fused_wall_seconds or 0.0

    @property
    def histogram(self) -> np.ndarray:
        if self.counts is not None:
            return self.counts.sum(axis=0, dtype=np.int64)
        return np.sum([k.histogram for k in self.kernels], axis=0)


@dataclasses.dataclass
class PendingWave:
    """An in-flight ``run_batch`` dispatch (``launch_batch``'s handle).

    ``outs``/``sides`` are unmaterialized jax arrays until
    ``finish_batch`` blocks on them; ``launched_at`` anchors the wave's
    launch->ready wall clock, so a wave that queued behind earlier
    in-flight work reports the wait it actually saw.
    """

    outs: Dict[str, jnp.ndarray]
    sides: list
    compiled: CompiledModel
    n_cc: int
    lanes: int
    wave_slots: int
    launched_at: float


def _side(res: DynasparseResult, counts: bool = False) -> tuple:
    """A kernel's side outputs from the fused program: (codes, dens_x,
    dens_y, out_density, fmt), and its writeback counts last where
    ``counts`` (the attention kernels, whose kept entries are counted)."""
    side = (res.codes, res.dens_x, res.dens_y, res.out_density, res.fmt)
    return side + (res.out_counts,) if counts else side


def _pack_report(kernels, flows, sides, profiles) -> tuple:
    """The single-inference program's report as a few packed arrays
    (traced): ``((hist, *buffers), layout)``.

    * ``hist`` -- (n_kernels + 1, 4) int32.  Row i is kernel i's primitive
      counts [SKIP, GEMM, SPDMM, SPMM], ``np.bincount`` of its codes; the
      last row holds the attention kernels' support entries and kept
      entries (their writeback counts), each summed over the heads.
    * ``buffers`` -- one flat array per dtype holding every kernel's
      ``_side`` fields (codes, dens_x, dens_y, out_density, fmt).
    * ``layout`` -- static, per kernel and field: (buffer, start, stop,
      shape) of that field in ``buffers``.
    """
    prims = jnp.arange(4, dtype=jnp.int32)
    rows = [jnp.sum(side[0].reshape(-1, 1) == prims, axis=0, dtype=jnp.int32)
            for side in sides]
    # the heads' sums reduced once: a chain of scalar adds costs one
    # device op per head
    att = [jnp.stack([jnp.sum(profiles[(pair[0].source, pair[0].block)]
                              .counts, dtype=jnp.int32),
                      jnp.sum(side[5], dtype=jnp.int32)])
           for k, pair, side in zip(kernels, flows, sides)
           if k.kernel_type == KernelType.ATTENTION]
    att = (jnp.sum(jnp.stack(att), axis=0, dtype=jnp.int32) if att
           else jnp.zeros(2, jnp.int32))
    hist = jnp.stack(rows + [jnp.pad(att, (0, 2))])

    parts: Dict[np.dtype, list] = {}      # dtype -> flat leaves, in order
    sizes: Dict[np.dtype, int] = {}
    layout = []
    for side in sides:
        slots = []
        for leaf in side[:5]:
            leaf = jnp.asarray(leaf)
            dtype = np.dtype(leaf.dtype)
            start = sizes.get(dtype, 0)
            parts.setdefault(dtype, []).append(leaf.reshape(-1))
            sizes[dtype] = start + leaf.size
            slots.append((list(parts).index(dtype), start, sizes[dtype],
                          tuple(leaf.shape)))
        layout.append(tuple(slots))
    buffers = tuple(jnp.concatenate(p) for p in parts.values())
    return (hist,) + buffers, tuple(layout)


class _PackedReport:
    """One inference's packed side outputs, left on the device until a
    field is first read, then copied to the host in one ``device_get``."""

    def __init__(self, buffers: tuple, layout: tuple):
        self.buffers, self.layout = buffers, layout
        self._host = None

    def field(self, kernel: int, field: int) -> np.ndarray:
        """Field ``field`` of ``_side`` of the ``kernel``-th kernel."""
        if self._host is None:
            spans.count("runtime.readbacks", 1)
            self._host = jax.device_get(self.buffers)
        buf, start, stop, shape = self.layout[kernel][field]
        return self._host[buf][start:stop].reshape(shape)


class _PackedField(Mapping):
    """``{kernel output: one field of its side outputs}`` over a
    :class:`_PackedReport`, read on access."""

    def __init__(self, packed: _PackedReport, outs: List[str], field: int):
        self._packed, self._field = packed, field
        self._index = {out: i for i, out in enumerate(outs)}

    def __getitem__(self, out: str) -> np.ndarray:
        return self._packed.field(self._index[out], self._field)

    def __iter__(self):
        return iter(self._index)

    def __len__(self) -> int:
        return len(self._index)


def _k2p_model_seconds(num_decisions: int) -> float:
    return num_decisions * _K2P_INSTRUCTIONS / _SOFT_PROC_IPS


# ---------------------------------------------------------------------------
# Pure cost-model simulation (paper-table benchmarks; no numerics).
# ---------------------------------------------------------------------------

def propagate_stats(
    compiled: CompiledModel,
    static_stats: Dict[str, SparsityStats],
    *,
    relu_keep: float = 0.5,
) -> Dict[str, SparsityStats]:
    """Forward pass in DENSITY space over the IR.

    Intermediate feature densities are unknown at compile time (the paper
    profiles them at runtime); here we predict them per block with the
    independent-Bernoulli model (perf_model.predict_output_density), which is
    also what the paper's Analyzer uses to pre-plan layer l+1 during layer l.
    ReLU keeps ``relu_keep`` of nonzeros (sign symmetry).
    """
    env = dict(static_stats)
    for k in compiled.graph.topo_order():
        if k.kernel_type == KernelType.ATTENTION:
            raise NotImplementedError(
                "attention kernels have no density-space model (their "
                "operand density is input-dependent by construction); GAT "
                "runs only through the real-numerics executor")
        dx, dy = _operand_block_densities(k, env)
        _, bk, _ = k.block_dims
        # out block (i, j): 1 - prod_k (1 - dx[i,k] dy[k,j])^bk
        log_stay = np.zeros((dx.shape[0], dy.shape[1]))
        for kk in range(dx.shape[1]):
            p = np.clip(np.outer(dx[:, kk], dy[kk, :]), 0.0, 1.0 - 1e-12)
            log_stay += bk * np.log1p(-p)
        dens = 1.0 - np.exp(log_stay)
        if k.kernel_type == KernelType.AGGREGATE:
            # stats convention: features live at (N2, N2) granularity; the
            # Aggregate result is uniform within its N1 row panel -> expand.
            dens = np.repeat(dens, max(k.scheme.n1 // k.scheme.n2, 1), axis=0)
            m = k.matmul_dims[0]
            dens = dens[: -(-m // k.scheme.n2)]
        if k.epilogue_add is not None and k.epilogue_add in env:
            other = env[k.epilogue_add].block_densities
            dens = 1.0 - (1.0 - dens) * (1.0 - other)
        if k.activation_enabled and k.activation == Activation.RELU:
            dens = dens * relu_keep
        m, _, d = k.matmul_dims
        env[k.out] = SparsityStats.from_predicted(
            (m, d), (k.scheme.n2, k.scheme.n2), dens)
    return env


def _pool_rows(bd: np.ndarray, r: int) -> np.ndarray:
    """Mean-pool row-blocks r at a time (exact for element densities)."""
    if r <= 1:
        return bd
    rows = bd.shape[0]
    pad = (-rows) % r
    if pad:
        bd = np.concatenate([bd, np.zeros((pad, bd.shape[1]))], axis=0)
        w = np.concatenate([np.ones((rows, 1)), np.zeros((pad, 1))])
    else:
        w = np.ones((bd.shape[0], 1))
    num = (bd * w).reshape(-1, r, bd.shape[1]).sum(axis=1)
    den = w.reshape(-1, r, 1).sum(axis=1)
    return num / np.maximum(den, 1)


def _operand_block_densities(k: KernelIR, env: Dict[str, SparsityStats]
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """(I, K) lhs / (K, J) rhs block-density grids at the kernel's dims.

    Feature-matrix stats are stored at (N2, N2); an Aggregate kernel consumes
    its rhs at (N1, N2) fiber granularity, so row-blocks are mean-pooled.
    """
    sx, sy = env[k.lhs], env[k.rhs]
    dx, dy = sx.block_densities, sy.block_densities
    if k.kernel_type == KernelType.AGGREGATE:
        dy = _pool_rows(dy, max(k.scheme.n1 // k.scheme.n2, 1))
    return dx, dy


def simulate_inference(
    compiled: CompiledModel,
    stats_env: Dict[str, SparsityStats],
    *,
    strategy: str = "dynamic",
    model: Optional[FPGACostModel] = None,
    n_cc: Optional[int] = None,
) -> InferenceReport:
    """Predicted latency of a full GNN inference under a mapping strategy.

    Pure cost-model execution, no numerics: ``stats_env`` maps every tensor
    name the IR references to its :class:`~repro.core.profiler.SparsityStats`
    -- compile-time-known tensors measured, runtime intermediates predicted
    by :func:`propagate_stats` (the independent-Bernoulli density
    propagation).  Stats follow the repo-wide granularity convention:
    adjacency at (N1, N1), features/weights at (N2, N2); Aggregate kernels
    mean-pool feature row-blocks to their (N1, N2) fiber granularity via
    ``_pool_rows`` inside ``_operand_block_densities``.

    Per kernel: host K2P planning (``analyzer.plan_kernel_host``, chunked
    so NELL-sized grids stay in memory), Alg. 8 dynamic scheduling over
    ``n_cc`` cores, and the Table IV cost under ``model``
    (``FPGACostModel`` for the paper's numbers, ``TPUCostModel`` for the
    TPU adaptation).  ``strategy`` follows the same contract as
    :class:`FusedModelExecutor`.  This is how the paper-table benchmarks
    evaluate graphs whose dense materialization would not fit this
    container (NELL/Reddit), mirroring how the paper's own latency derives
    from its model + measured densities + Alg. 8 load balance.
    """
    model = model or FPGACostModel()
    n_cc = n_cc or compiled.partition.n_cc
    reports = []
    for k in compiled.graph.topo_order():
        if k.kernel_type == KernelType.ATTENTION:
            raise NotImplementedError(
                "attention kernels have no density-space cost model; GAT "
                "runs only through the real-numerics executor")
        dx, dy = _operand_block_densities(k, stats_env)
        codes, costs = analyzer.plan_kernel_host(
            strategy, dx, dy, k.block_dims, model,
            kernel_type=k.kernel_type)
        sched = scheduler.schedule_dynamic(costs.reshape(-1), n_cc)
        hist = np.bincount(codes.reshape(-1), minlength=4).astype(np.int64)
        reports.append(KernelReport(
            name=k.name, num_tasks=int(costs.size), histogram=hist,
            makespan_cycles=sched.makespan, utilization=sched.utilization,
            k2p_seconds=_k2p_model_seconds(codes.size)))
    return InferenceReport(reports, strategy)


# ---------------------------------------------------------------------------
# Real-numerics execution.
# ---------------------------------------------------------------------------

_AGG_PRE = {AggOp.SUM: "A", AggOp.MEAN: "A_mean"}


def _agg_lhs_name(k: KernelIR) -> str:
    """Env name of an Aggregate kernel's lhs operand.

    The adjacency-shaped lhs "A" rebinds to the normalization the agg op
    needs (A or A_mean); a PRODUCED lhs (the GAT attention matrix, already
    edge-softmax-normalized) binds by its own name."""
    if k.lhs != "A":
        return k.lhs
    name = _AGG_PRE.get(k.agg_op)
    if name is None:
        raise NotImplementedError(
            f"{k.agg_op} aggregation is not matmul-representable")
    return name


def _bookkeep_kernel(k: KernelIR, codes, dens_x, dens_y, n_cc: int, model
                     ) -> KernelReport:
    """One kernel's report row from the planner's codes (the MicroBlaze's
    role): Table IV per-task costs, Alg. 8 scheduling, primitive histogram,
    modeled + measured K2P time."""
    codes = np.asarray(codes)
    dx = np.asarray(dens_x)
    dy = np.asarray(dens_y)
    t_plan = time.perf_counter()
    costs = analyzer.task_costs_host(codes, dx, dy, k.block_dims, model)
    sched = scheduler.schedule_dynamic(costs.reshape(-1), n_cc)
    hist = np.bincount(codes.reshape(-1), minlength=4).astype(np.int64)
    k2p_wall = time.perf_counter() - t_plan
    return KernelReport(
        name=k.name, num_tasks=int(costs.size), histogram=hist,
        makespan_cycles=sched.makespan, utilization=sched.utilization,
        k2p_seconds=_k2p_model_seconds(codes.size),
        k2p_wall_seconds=k2p_wall, dens_x=dx, dens_y=dy)


def _count_attention(kernels, support: int, kept: int) -> None:
    """Counters of one inference's attention kernels, while a profiler
    runs: ``attention.heads`` kernels, ``attention.support`` entries of
    the adjacency support summed over them, ``attention.kept`` entries
    with a weight above zero (from their writeback counts); the sums are
    the last row of the program's packed histogram."""
    heads = sum(k.kernel_type == KernelType.ATTENTION for k in kernels)
    if heads:
        spans.count("attention.heads", heads)
        spans.count("attention.support", support)
        spans.count("attention.kept", kept)


# ---------------------------------------------------------------------------
# Fused whole-model executor: ONE jit-compiled program per inference.
# ---------------------------------------------------------------------------

class FusedModelExecutor:
    """Traces a full ``CompiledModel`` into one jit-compiled program.

    The only code that executes a compiled model.  It walks the
    topologically-ordered kernel list inside a SINGLE trace and chains the
    writeback profiles between layers:

    * graph inputs (adjacency, features, weights) are profiled ONCE per
      (tensor identity, granularity) on the host and handed to the program
      as arguments -- the paper's split, where the COMPILER profiles the
      compile-time-known tensors and the runtime only ever profiles
      intermediates (Section IV); repeated inferences re-use the cached
      input profiles;
    * every intermediate is NEVER re-profiled -- its producer's
      ``out_counts`` writeback profile (at the repo-wide (N2, N2) feature
      granularity) is pooled to the consumer's operand granularity by
      ``profiler.BlockProfile.pool_rows`` (an exact integer sum, bitwise
      equal to direct profiling) and fed to
      ``analyzer.plan_codes_from_profiles``.

    Kernel l+1's K2P decision therefore depends only on kernel l's profile,
    which XLA emits at l's writeback -- so the planning of l+1 can be
    scheduled concurrently with l's task loop.  This is the paper's
    soft-processor/accelerator K2P-execution overlap (Section V-B2)
    realized as dataflow inside one program, with no host round-trip
    between layers.  ``InferenceReport.k2p_exposed_seconds`` models the
    resulting overlapped soft-processor time.

    ``strategy`` is one of ``analyzer.STRATEGIES``: ``"dynamic"``
    (Algorithm 7), ``"s1"`` (Aggregate->SpDMM / Update->GEMM), ``"s2"``
    (all SpDMM) or ``"gemm"`` (all dense); outputs are value-identical
    across strategies (dispatch changes cost, never results).
    ``use_kernels`` routes the non-SKIP steps through the Pallas
    block-sparse kernels with ``tile``/``unroll`` (interpret mode off-TPU).

    Intermediate feature matrices live only inside the XLA program (they
    are temporaries, reused by buffer assignment, and are not returned
    unless ``keep_intermediates=True``); set ``donate=True`` to also donate
    the input tensor buffers when the caller will not reuse them.

    The report (``InferenceReport``: histograms, Alg. 8 makespan, modeled
    K2P time) derives from the planner's codes.  The single-inference
    program counts each kernel's primitives itself and packs every
    kernel's codes and densities into one buffer per dtype, so ``run``
    copies only the small histogram to the host and builds the per-kernel
    rows on their first read.  ``collect_report=False`` drops the report
    altogether (the wave path still returns per-slot side outputs, which
    it then leaves on the device).

    ``run`` returns an env dict holding the final output plus the report
    (``models.gnn.DenseGNN.run`` wraps it).  ``run_batch`` is the
    multi-tenant surface on top: one jitted call serving a stacked WAVE of
    inferences over shared weights (``serving.graph_engine`` is the
    request loop that feeds it).
    """

    def __init__(self, *, strategy: str = "dynamic",
                 model: Optional[FPGACostModel] = None,
                 n_cc: Optional[int] = None,
                 use_kernels: bool = False,
                 tile: Tuple[int, int] = (16, 16),
                 unroll: int = 1,
                 keep_intermediates: bool = False,
                 donate: bool = False,
                 keep_codes: bool = False,
                 collect_report: bool = True,
                 format_aware: bool = True,
                 csr_rmax: int = 64):
        self.strategy = strategy
        self.model = model or FPGACostModel()
        self.n_cc = n_cc
        self.use_kernels = use_kernels
        self.tile = tile
        self.unroll = unroll
        self.keep_intermediates = keep_intermediates
        self.donate = donate
        self.keep_codes = keep_codes
        # format-aware K2P (DESIGN.md section 13): inert under the default
        # FPGACostModel, active under TPUCostModel.  The walk SHARES one
        # on-the-fly conversion between kernels reading the same source
        # tensor (see _trace_kernels).
        self.format_aware = format_aware
        self.csr_rmax = csr_rmax
        # serving knob: False skips the report -- no histogram readback
        # (except for the counters while a profiler runs), no per-kernel
        # rows, so no O(I*J*K) cost prediction and no Alg. 8 scheduling
        # even when asked for; finish_batch then copies no code grids.  The
        # report holds no kernel entries, only the fused wall clock.
        self.collect_report = collect_report
        # one jitted whole-model program per (model structure, tensor
        # signature); cache hits re-launch without re-tracing.
        self._programs: Dict[tuple, tuple] = {}
        # host-side input-profile cache: (env name, granularity) ->
        # (tensor ref, BlockProfile).  The ref keeps the array alive so the
        # identity check is sound; a caller passing fresh tensor VALUES
        # (same shapes) gets re-profiled automatically.
        self._input_profiles: Dict[tuple, tuple] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        # incremented inside the traced function: counts actual traces, not
        # launches (the one-jitted-call-per-inference contract is tested).
        self.trace_count = 0
        # after ``run``: views of its packed report, copied on first read
        self.profiled_densities: Mapping[str, np.ndarray] = {}
        self.planned_codes: Mapping[str, np.ndarray] = {}
        self.planned_formats: Mapping[str, np.ndarray] = {}

    # -- program construction ----------------------------------------------
    @staticmethod
    def _tensor_sig(tensors: Dict[str, jnp.ndarray]) -> tuple:
        # shape/dtype read directly: numpy and jax arrays both carry them,
        # and jnp.asarray here would device-copy host-side wave stacks
        # just to build a cache key
        return tuple(sorted((name, tuple(v.shape), str(v.dtype))
                            for name, v in tensors.items()))

    def _signature(self, compiled: CompiledModel,
                   tensors: Dict[str, jnp.ndarray]) -> tuple:
        ks = tuple(
            (k.name, k.kernel_type, k.block_dims, k.scheme.n2, k.lhs, k.rhs,
             k.out, k.agg_op.value, k.epilogue_add, k.epilogue_scale,
             k.activation.value if k.activation_enabled else "none",
             k.att_src, k.att_dst, k.att_slope, k.att_threshold,
             k.heads_in, k.head_mean)
            for k in compiled.graph.topo_order())
        return (ks, self._tensor_sig(tensors))

    @staticmethod
    def _resolved_flows(compiled: CompiledModel):
        """Per-kernel (lhs, rhs) OperandFlows with Aggregate lhs rebound to
        its env name ("A"/"A_mean"; the IR names it "A"); None for a
        HEAD_COMBINE kernel, which plans nothing."""
        out = []
        for k, pair in zip(compiled.graph.topo_order(),
                           compiled.graph.operand_flows()):
            if k.kernel_type == KernelType.AGGREGATE:
                fx, fy = pair
                pair = (dataclasses.replace(fx, source=_agg_lhs_name(k)), fy)
            out.append(pair)
        return out

    @staticmethod
    def _needed_inputs(flows) -> List[tuple]:
        """Ordered unique (env name, granularity) of every graph-input
        profile the program consumes (profiled host-side, passed in)."""
        seen: List[tuple] = []
        for pair in flows:
            for f in pair or ():
                key = (f.source, f.block)
                if f.producer is None and key not in seen:
                    seen.append(key)
        return seen

    def _trace_kernels(self, kernels, flows, env: Dict[str, jnp.ndarray],
                       profiles: Dict[tuple, profiler.BlockProfile]) -> list:
        """The shared fused trace body (single-inference AND batched-wave
        programs): walk the topo-ordered kernels, planning each from
        ``profiles`` (graph inputs) or the producer's chained writeback
        counts.  Mutates ``env`` with every kernel's output and returns the
        per-kernel side outputs (``_side``).

        Format sharing: when two kernels read the same source tensor (both
        aggregates of a 2-layer GCN read "A"), the fused walk converts it
        at most ONCE -- the first kernel that wants CSR pays the D2S, later
        kernels reuse the view (a second cond converts only if no earlier
        kernel did).  The conversion is deterministic, so the reused view is
        bitwise what a kernel converting for itself would build, and each
        kernel's DECISION still charges the full transform cost (see
        ``TPUCostModel.select_format_traced``) so decisions stay a pure
        function of the densities."""
        counts_env: Dict[str, profiler.BlockProfile] = {}
        # (source name, shape) -> (want so far, shared ELL view)
        ell_env: Dict[tuple, tuple] = {}
        sides = []
        for k, pair in zip(kernels, flows):
            # stable names for the device trace: the kernel (aggregate,
            # update, attention or head_combine), and inside it k2p_plan,
            # d2s and the writeback_profile
            with jax.named_scope(k.kernel_type.name.lower()):
                if k.kernel_type == KernelType.HEAD_COMBINE:
                    n2c = k.scheme.n2
                    res = head_combine(
                        tuple(env[name] for name in k.heads_in),
                        (env[k.epilogue_add]
                         if k.epilogue_add is not None else None),
                        mean=k.head_mean,
                        activation=(k.activation.value
                                    if k.activation_enabled else "none"),
                        out_block=(n2c, n2c))
                    env[k.out] = res.out
                    counts_env[k.out] = profiler.BlockProfile(
                        res.out_counts, res.out.shape, (n2c, n2c))
                    sides.append(_side(res))
                    continue
                fx, fy = pair
                x, y = env[fx.source], env[fy.source]
                if k.kernel_type == KernelType.ATTENTION:
                    # masked edge-softmax (GAT): no K2P planning of its own
                    # -- its whole point is that the OUTPUT density is
                    # unknowable before execution.  The writeback profile
                    # it emits is what the downstream Aggregate plans from,
                    # per head.
                    n2a = k.scheme.n2
                    res = attention_adjacency(
                        x, y, env[k.att_src], env[k.att_dst],
                        slope=k.att_slope, threshold=k.att_threshold,
                        out_block=(n2a, n2a))
                    env[k.out] = res.out
                    counts_env[k.out] = profiler.BlockProfile(
                        res.out_counts, res.out.shape, (n2a, n2a))
                    sides.append(_side(res, counts=True))
                    continue
                prof_x, prof_y = (
                    counts_env[f.source].pool_rows(f.pool_rows)
                                        .pool_cols(f.pool_cols)
                    if f.producer is not None
                    else profiles[(f.source, f.block)]
                    for f in (fx, fy))
                fmt = None
                ell = None
                with jax.named_scope("k2p_plan"):
                    codes, dens_x, dens_y = analyzer.plan_codes_from_profiles(
                        self.strategy, prof_x, prof_y, self.model,
                        kernel_type=k.kernel_type)
                    if self.format_aware:
                        fmt = analyzer.plan_format(
                            self.strategy, dens_x, dens_y, x.shape,
                            y.shape[1], k.block_dims, self.model,
                            kernel_type=k.kernel_type, rmax=self.csr_rmax)
                if fmt is not None:
                    ekey = (fx.source, tuple(x.shape))
                    prev = ell_env.get(ekey)
                    with jax.named_scope("d2s"):
                        if prev is None:
                            ell = ell_when(fmt, x, self.csr_rmax)
                            want = fmt
                        else:
                            prev_want, prev_ell = prev
                            ell = jax.lax.cond(
                                jnp.logical_and(fmt == Format.CSR,
                                                prev_want != Format.CSR),
                                lambda x=x: _formats.dense_to_ell(
                                    x, rmax=self.csr_rmax),
                                lambda: prev_ell)
                            want = jnp.maximum(prev_want, fmt)
                    ell_env[ekey] = (want, ell)
                residual = (env[k.epilogue_add]
                            if k.epilogue_add is not None else None)
                n2 = k.scheme.n2
                res = dynasparse_matmul(
                    x, y, codes=codes, dens_x=dens_x, dens_y=dens_y,
                    fmt=fmt, ell=ell,
                    residual=residual, strategy=self.strategy,
                    kernel_type=k.kernel_type,
                    epilogue_scale=(k.epilogue_scale
                                    if residual is not None else 1.0),
                    activation=(k.activation.value
                                if k.activation_enabled else "none"),
                    out_block=(n2, n2), block=k.block_dims,
                    cost_model=self.model, use_kernels=self.use_kernels,
                    tile=self.tile, unroll=self.unroll,
                    format_aware=self.format_aware, csr_rmax=self.csr_rmax)
                env[k.out] = res.out
                counts_env[k.out] = profiler.BlockProfile(
                    res.out_counts, res.out.shape, (n2, n2))
                sides.append(_side(res))
        return sides

    def _build(self, compiled: CompiledModel) -> tuple:
        """``(fn, needed, layout)``: the jitted program, the graph-input
        profiles it takes, and the layout of its packed report
        (``_pack_report``), filled in when ``fn`` is traced."""
        kernels = compiled.graph.topo_order()
        flows = self._resolved_flows(compiled)
        needed = self._needed_inputs(flows)
        final = kernels[-1].out
        layout = []

        def fused(tensors, in_counts):
            self.trace_count += 1          # runs at trace time only
            env = dict(tensors)
            profiles: Dict[tuple, profiler.BlockProfile] = {
                (name, blk): profiler.BlockProfile(
                    counts, tuple(env[name].shape), blk)
                for (name, blk), counts in zip(needed, in_counts)}
            sides = self._trace_kernels(kernels, flows, env, profiles)
            with jax.named_scope("report"):
                packed, layout[:] = _pack_report(kernels, flows, sides,
                                                 profiles)
            outs = (dict(env) if self.keep_intermediates
                    else {final: env[final]})
            return outs, packed

        fn = jax.jit(fused, donate_argnums=(0,) if self.donate else ())
        return fn, needed, layout

    def _build_batch(self, compiled: CompiledModel, shared_needed: tuple,
                     request_needed: tuple, lanes: Optional[int] = None):
        """One jitted program per (model, shared shapes, wave shapes, lane
        count): a ``lax.scan`` over the stacked per-request tensors whose
        body is the same fused kernel walk as the single-inference program.
        Shared tensors (weights) ride in as scan constants with host-cached
        profiles; per-request graph inputs are profiled INSIDE the program
        (``profiler.batched_block_counts``, one fused reduction per
        (tensor, granularity) for the whole wave) -- each request is a new
        graph, so its profiling is the runtime's job, not the host's.

        With ``lanes`` (a device-group size) the scan body is
        ``shard_map``-ed over the request axis: every device runs the
        identical scan over ITS slice of the wave -- chips as the paper's
        Computation Cores, the Alg. 8 task queue split by the caller's
        cost-aware bins (``core.scheduler.assign_bins``).  The program is
        traced against the ABSTRACT ``lanes``-device cores mesh
        (``distributed.sharding.abstract_cores_mesh``), never a concrete
        device list: the concrete devices bind at call time from the
        batched inputs' shardings, so disjoint same-size submeshes
        (``partition_mesh`` groups) all reuse this one program.  Requests
        are independent (the scan carries nothing), so no collectives are
        needed and per-request numerics are unchanged."""
        kernels = compiled.graph.topo_order()
        flows = self._resolved_flows(compiled)
        final = kernels[-1].out

        def wave_body(shared, shared_counts, batched):
            base: Dict[tuple, profiler.BlockProfile] = {
                (name, blk): profiler.BlockProfile(
                    counts, tuple(shared[name].shape), blk)
                for (name, blk), counts in zip(shared_needed, shared_counts)}
            wave_counts = tuple(
                profiler.batched_block_counts(batched[name], blk)
                for name, blk in request_needed)

            def one(_, xs):
                req, req_counts = xs
                env = {**shared, **req}
                profiles = dict(base)
                for (name, blk), counts in zip(request_needed, req_counts):
                    profiles[(name, blk)] = profiler.BlockProfile(
                        counts, tuple(env[name].shape), blk)
                sides = self._trace_kernels(kernels, flows, env, profiles)
                outs = ({k.out: env[k.out] for k in kernels}
                        if self.keep_intermediates else {final: env[final]})
                return None, (outs, sides)

            _, (outs, sides) = jax.lax.scan(one, None, (batched, wave_counts))
            return outs, sides

        if lanes is not None:
            # shared + profiles replicated, the request axis sharded in AND
            # out; check_vma off because the per-shard scans never touch a
            # replicated output.
            body = jax.shard_map(
                wave_body, mesh=dist_sharding.abstract_cores_mesh(lanes),
                in_specs=(PartitionSpec(), PartitionSpec(),
                          dist_sharding.wave_spec()),
                out_specs=dist_sharding.wave_spec(),
                check_vma=False)
        else:
            body = wave_body

        def fused_wave(shared, shared_counts, batched):
            self.trace_count += 1          # runs at trace time only
            return body(shared, shared_counts, batched)

        return jax.jit(fused_wave, donate_argnums=(2,) if self.donate else ())

    def _program(self, compiled: CompiledModel,
                 tensors: Dict[str, jnp.ndarray]) -> tuple:
        """``(fn, needed)`` of the single-inference program."""
        return self._program_entry(compiled, tensors)[:2]

    def _program_entry(self, compiled: CompiledModel,
                       tensors: Dict[str, jnp.ndarray]) -> tuple:
        key = self._signature(compiled, tensors)
        entry = self._programs.get(key)
        if entry is not None:
            self.cache_hits += 1
            return entry
        self.cache_misses += 1
        entry = self._build(compiled)
        self._programs[key] = entry
        return entry

    def _input_counts(self, needed, tensors) -> Tuple[jnp.ndarray, ...]:
        """The graph-input profiles, measured once per tensor identity
        (the compiler's static-profiling role; intermediates are profiled
        by the program itself, fused at writeback)."""
        out = []
        for name, blk in needed:
            arr = tensors[name]
            cached = self._input_profiles.get((name, blk))
            if cached is None or cached[0] is not arr:
                cached = (arr, profiler.BlockProfile.measure(arr, blk))
                self._input_profiles[(name, blk)] = cached
            out.append(cached[1].counts)
        return tuple(out)

    # -- execution ----------------------------------------------------------
    def run(self, compiled: CompiledModel, tensors: Dict[str, jnp.ndarray]
            ) -> Tuple[Dict[str, jnp.ndarray], InferenceReport]:
        """One whole-model inference = one jitted call.

        Returns ``(env, report)`` where ``env`` holds the final output (all
        intermediates too iff ``keep_intermediates=True``) and ``report``
        carries the per-kernel bookkeeping plus ``fused_wall_seconds`` (the
        program's wall clock).

        The program returns its report packed (``_pack_report``); the
        report copies only the primitive histogram to the host.  The
        per-kernel rows, ``profiled_densities`` and (with ``keep_codes``)
        ``planned_codes``/``planned_formats`` read the packed buffers on
        first access, which copies them in one more transfer.
        """
        n_cc = self.n_cc or compiled.partition.n_cc
        with spans.span("runtime.dispatch"):
            fn, needed, layout = self._program_entry(compiled, tensors)
            in_counts = self._input_counts(needed, tensors)
            t0 = time.perf_counter()
            outs, (hist, *buffers) = fn(tensors, in_counts)
        with spans.span("runtime.wait"):
            jax.block_until_ready((outs, hist, buffers))
        wall = time.perf_counter() - t0

        with spans.span("runtime.report"):
            kernels = compiled.graph.topo_order()
            packed = _PackedReport(tuple(buffers), layout)
            names = [k.out for k in kernels]
            self.profiled_densities = _PackedField(packed, names, 3)
            if self.keep_codes:
                self.planned_codes = _PackedField(packed, names, 0)
                self.planned_formats = _PackedField(packed, names, 4)
            rows, counts = [], None
            if self.collect_report or spans.enabled():
                spans.count("runtime.readbacks", 1)
                host = np.asarray(hist)
                _count_attention(kernels, int(host[-1, 0]), int(host[-1, 1]))
            if self.collect_report:
                counts = host[:-1]
                rows = functools.partial(self._report_rows, kernels, packed,
                                         n_cc, self.model)
        return outs, InferenceReport(rows, self.strategy,
                                     fused_wall_seconds=wall, counts=counts)

    @staticmethod
    def _report_rows(kernels, packed: _PackedReport, n_cc: int, model
                     ) -> List[KernelReport]:
        """The per-kernel rows of one ``run``, from its packed report."""
        spans.count("runtime.report_rows", 1)
        return [_bookkeep_kernel(k, packed.field(i, 0), packed.field(i, 1),
                                 packed.field(i, 2), n_cc, model)
                for i, k in enumerate(kernels)]

    # -- batched (multi-tenant) execution -----------------------------------
    def launch_batch(self, compiled: CompiledModel,
                     shared: Dict[str, jnp.ndarray],
                     batched: Dict[str, jnp.ndarray],
                     mesh: Optional[Mesh] = None) -> "PendingWave":
        """Dispatch one wave WITHOUT blocking: the asynchronous half of
        :meth:`run_batch`.

        Returns a :class:`PendingWave` whose arrays are in flight; pass it
        to :meth:`finish_batch` to block and collect ``(outs, report)``.
        The split lets a serving layer keep several waves in the XLA
        queue while the host pads the next one (``serving.scheduler``'s
        dispatch lanes); the pending wave's wall clock runs from launch to
        ready, so queue time behind earlier in-flight waves is measured,
        not hidden."""
        with spans.span("runtime.dispatch"):
            n_cc = self.n_cc or compiled.partition.n_cc
            flows = self._resolved_flows(compiled)
            needed = self._needed_inputs(flows)
            missing = [n for n, _ in needed
                       if n not in shared and n not in batched]
            if missing:
                raise KeyError(f"wave inputs missing tensors: {missing}")
            shared_needed = tuple((n, b) for n, b in needed if n in shared)
            request_needed = tuple((n, b) for n, b in needed if n in batched)

            lanes = 1
            if mesh is not None:
                if (len(mesh.axis_names) != 1
                        or mesh.axis_names[0] != dist_sharding.CORES_AXIS):
                    raise ValueError(
                        f"run_batch mesh must be 1-D over "
                        f"{dist_sharding.CORES_AXIS!r}, got {mesh.axis_names}")
                lanes = int(mesh.devices.size)
                b = int(next(iter(batched.values())).shape[0])
                if b % lanes:
                    raise ValueError(
                        f"wave of {b} slots not divisible by {lanes} mesh "
                        f"devices")

            # the shard_map program is traced against the ABSTRACT cores
            # mesh (concrete devices bind at call time from the inputs'
            # shardings), so the key carries only the GROUP SIZE: disjoint
            # same-size device groups -- partition_mesh lanes -- share one
            # compiled program, and the trace bound is one per (bucket,
            # group size).
            key = ("wave", None if mesh is None else lanes,
                   self._signature(compiled, shared),
                   self._tensor_sig(batched))
            fn = self._programs.get(key)
            if fn is not None:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
                fn = self._build_batch(compiled, shared_needed, request_needed,
                                       lanes=None if mesh is None else lanes)
                self._programs[key] = fn

            if mesh is not None:
                # commit the stacked request tensors to their wave sharding
                # up front: host-side stacks transfer as one host->shard
                # split per device instead of staging the full stack on one
                # device and resharding from there.
                batched = jax.device_put(
                    batched, dist_sharding.wave_shardings(mesh, batched))

            shared_counts = self._input_counts(shared_needed, shared)
            b_sz = int(next(iter(batched.values())).shape[0])
            t0 = time.perf_counter()
            outs, sides = fn(shared, shared_counts, batched)
            return PendingWave(outs=outs, sides=sides, compiled=compiled,
                               n_cc=n_cc, lanes=lanes, wave_slots=b_sz,
                               launched_at=t0)

    def finish_batch(self, pending: "PendingWave"
                     ) -> Tuple[Dict[str, jnp.ndarray], InferenceReport]:
        """Block on a :meth:`launch_batch` wave and assemble its report
        (the synchronous half of :meth:`run_batch`)."""
        outs, sides = pending.outs, pending.sides
        with spans.span("runtime.wait"):
            jax.block_until_ready((outs, sides))
        wall = time.perf_counter() - pending.launched_at

        with spans.span("runtime.report"):
            topo = pending.compiled.graph.topo_order()
            self.profiled_densities = {
                k.out: side[3] for k, side in zip(topo, sides)}  # (B, ...)
            if self.keep_codes:
                self.planned_codes = {
                    k.out: np.asarray(side[0])
                    for k, side in zip(topo, sides)}
                self.planned_formats = {
                    k.out: np.asarray(side[4])  # (B,) executed Format/slot
                    for k, side in zip(topo, sides)}
            reports = []
            if self.collect_report:
                for b in range(pending.wave_slots):
                    for k, side in zip(topo, sides):
                        rep = _bookkeep_kernel(
                            k, side[0][b], side[1][b], side[2][b],
                            pending.n_cc, self.model)
                        rep.name = f"{k.name}[{b}]"
                        reports.append(rep)
        return outs, InferenceReport(reports, self.strategy,
                                     fused_wall_seconds=wall,
                                     wave_slots=pending.wave_slots,
                                     wave_lanes=pending.lanes)

    def run_batch(self, compiled: CompiledModel,
                  shared: Dict[str, jnp.ndarray],
                  batched: Dict[str, jnp.ndarray],
                  mesh: Optional[Mesh] = None
                  ) -> Tuple[Dict[str, jnp.ndarray], InferenceReport]:
        """One jitted call serving a WAVE of stacked inferences.

        The multi-tenant entry point behind ``serving.graph_engine``
        (:meth:`launch_batch` + :meth:`finish_batch`; use the split pair
        directly to keep several waves in flight):

        * ``shared`` -- tensors common to every request of the wave (the
          model weights), profiled once per tensor identity on the host
          (same ``_input_profiles`` cache as ``run``, so steady-state waves
          never re-profile them);
        * ``batched`` -- per-request tensors stacked on a leading batch
          axis (adjacency, features: ``(B, ...)``), profiled inside the
          program and scanned over, each request planning its own K2P codes
          from its own density profile through the same chained-writeback
          walk as the single-inference program.

        Returns ``(outs, report)`` where every entry of ``outs`` is stacked
        ``(B, ...)`` and ``report`` is WAVE-level: ``fused_wall_seconds`` is
        the one dispatch's wall clock, and (with ``collect_report=True``)
        ``kernels`` holds per-request bookkeeping entries named
        ``"{kernel}[b]"``.  With ``donate=True`` the stacked request
        buffers are OFFERED for donation; XLA reuses them in place only
        when an output can alias them (the CPU backend often cannot and
        says so with a "donated buffers were not usable" UserWarning --
        donation is an optimization, never a correctness knob).  Programs
        cache per (model structure, shared signature, wave signature,
        lane count) -- a serving engine that pads waves to a fixed slot
        count gets exactly one trace per (shape bucket, lane count).

        ``mesh`` (a 1-D ``cores`` mesh from ``distributed.sharding
        .cores_mesh``, or any disjoint submesh of one from
        ``distributed.sharding.partition_mesh``) shards the wave's request
        axis across its devices: device d scans slots ``[d*B/D,
        (d+1)*B/D)``, so the caller should place requests into slots by
        cost-aware bins (``core.scheduler.assign_bins``;
        ``serving.graph_engine`` does).  Requires ``B % D == 0``.  Outputs
        are bitwise-identical to the unsharded program -- sharding splits
        the task queue, never the numerics -- which collapses to the same
        single-lane scan on a 1-device mesh.  Programs are traced against
        the abstract D-device mesh, so every same-size device group reuses
        one compiled program (one trace per (bucket, group size)).
        """
        return self.finish_batch(
            self.launch_batch(compiled, shared, batched, mesh=mesh))
