"""The unified Dynasparse executor: profile -> plan -> execute in one ``jit``.

This is the single execution path for the paper's mechanism -- the GNN
executor (``core.runtime.FusedModelExecutor``) inlines it for every
matmul kernel of its whole-model program, and the LM layers
(``models.layers``) call it too.  The whole pipeline --

    profile block densities  ->  plan_codes (any strategy, traced)  ->
    one batched block contraction that applies the plan as a mask  ->
    fused epilogue (residual + scale + activation)  ->
    result block-density profile fused at writeback

-- is traced once per (shapes, block, strategy, epilogue) signature.  On
the XLA path every non-SKIP primitive is the same dense block product, so
the plan only decides which (i, j, k) block products enter the sum: the
lhs blocks of SKIP steps are selected to zero and all I*J*K products run
as one ``dot_general`` on the MXU, with no sequential loop over tasks.
With ``use_kernels=True`` the non-SKIP steps instead call the Pallas
block-sparse kernels one block pair at a time, in a ``lax.scan`` over
output tasks with a ``lax.switch`` per reduction step, whose clamped-index
masked loops additionally scale *within-block* cost by tile density (the
TPU-granularity analogue of the FPGA's element-granularity skipping; see
DESIGN.md section 2).

The planner can also be bypassed: pass precomputed ``codes`` (e.g. planned
from layer l's writeback density profile while layer l executes -- the
paper's K2P/execution overlap, Section V-B2) and the executor applies
them verbatim.  The ``out_density`` side output is what feeds that
next-layer plan: it is computed from the value being written anyway, so XLA
fuses the counting into the producing kernel (the FPGA's comparator array at
the Result Buffer port).

Each (i, j) output block is one "task" of Algorithm 8; on a real mesh the
serving layer shards whole requests over chips by ``shard_map`` so chips
play the role of Computation Cores.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import analyzer, formats, profiler
from repro.core.ir import KernelType
from repro.core.perf_model import FPGACostModel, Format, Primitive, TPUCostModel
from repro.kernels import ops


@dataclasses.dataclass
class DynasparseResult:
    out: jnp.ndarray
    codes: jnp.ndarray          # (I, J, K) int32 Primitive per reduction step
    dens_x: jnp.ndarray         # (I, K) block densities of X
    dens_y: jnp.ndarray         # (K, J) block densities of Y
    out_density: jnp.ndarray    # block densities of the (post-epilogue) result
    # nonzero COUNTS of the result at ``out_block`` granularity -- the exact,
    # granularity-composable form of ``out_density`` that the fused
    # whole-model executor chains into the next layer's planner
    # (``profiler.BlockProfile``); integer sums pool bitwise-exactly across
    # mismatched block schemes where mean-pooled densities would not.
    out_counts: jnp.ndarray
    # () int32 perf_model.Format actually EXECUTED (CSR only when the planner
    # chose it AND the lossless rmax fit held at runtime); 0 whenever the
    # kernel is statically dense.
    fmt: jnp.ndarray


jax.tree_util.register_pytree_node(
    DynasparseResult,
    lambda r: ((r.out, r.codes, r.dens_x, r.dens_y, r.out_density,
                r.out_counts, r.fmt), None),
    lambda _, leaves: DynasparseResult(*leaves),
)


def ell_when(want: jnp.ndarray, x: jnp.ndarray, rmax: int) -> formats.ELLMatrix:
    """Convert ``x`` to its ELL view iff ``want`` selects CSR (traced).

    The zero branch keeps the cond cheap: a DENSE decision pays no
    conversion work at runtime, only the (static-shape) zero fill.
    """
    def _conv():
        return formats.dense_to_ell(x, rmax=rmax)

    def _zero():
        return formats.ELLMatrix(
            jnp.zeros((x.shape[0], rmax), x.dtype),
            jnp.zeros((x.shape[0], rmax), jnp.int32),
            jnp.zeros((x.shape[0],), jnp.int32), x.shape)

    return jax.lax.cond(want == Format.CSR, _conv, _zero)


def _blocked_density(xb: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Per-block density of a blocked tensor -- same normalization as
    ``profiler.block_density``, so the traced planner sees the same numbers
    as the host planner/simulator on ragged edge blocks."""
    counts = jnp.sum(xb != 0, axis=(2, 3))
    return profiler.density_from_counts(counts, m, n,
                                        xb.shape[2], xb.shape[3])


def _activate(out: jnp.ndarray, activation: str) -> jnp.ndarray:
    """The writeback epilogue's activation (``ir.Activation`` values)."""
    if activation == "relu":
        return jax.nn.relu(out)
    if activation == "prelu":
        return jnp.where(out >= 0, out, 0.25 * out)
    if activation == "elu":
        return jax.nn.elu(out)
    if activation != "none":
        raise ValueError(f"unknown activation {activation!r}")
    return out


@functools.partial(
    jax.jit,
    static_argnames=("strategy", "kernel_type", "epilogue_scale",
                     "activation", "out_block", "block", "cost_model",
                     "use_kernels", "tile", "unroll", "format_aware",
                     "csr_rmax"))
def dynasparse_matmul(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    codes: Optional[jnp.ndarray] = None,
    dens_x: Optional[jnp.ndarray] = None,
    dens_y: Optional[jnp.ndarray] = None,
    fmt: Optional[jnp.ndarray] = None,
    ell: Optional[formats.ELLMatrix] = None,
    residual: Optional[jnp.ndarray] = None,
    strategy: str = "dynamic",
    kernel_type: Optional[KernelType] = None,
    epilogue_scale: float = 1.0,
    activation: str = "none",
    out_block: Optional[Tuple[int, int]] = None,
    block: Tuple[int, int, int] = (128, 128, 128),
    cost_model=FPGACostModel(),
    use_kernels: bool = False,
    tile: Tuple[int, int] = (128, 128),
    unroll: int = 1,
    format_aware: bool = False,
    csr_rmax: int = 64,
) -> DynasparseResult:
    """``x @ y`` with per-(partition pair) primitive plan + fused epilogue.

    block = (bm, bk, bn): X is partitioned (bm x bk), Y (bk x bn) -- the
    paper's N1/N2 partitions, an (I, J, K) grid of block products.
    ``strategy`` picks the K2P rule: ``dynamic`` runs Algorithm 7 through
    ``cost_model.select_traced`` (Table IV rule or the TPU tile-density
    rule); ``s1``/``s2``/``gemm`` are the static baselines (``s1`` needs
    ``kernel_type``).

    Planner bypasses (both are how the paper overlaps K2P with execution,
    Section V-B2):

    * ``codes`` -- a precomputed (I, J, K) int32 Primitive grid is applied
      verbatim; the in-trace planner does not run.
    * ``dens_x`` / ``dens_y`` -- precomputed operand block densities at the
      CONSUMER granularity ((I, K) for X at (bm, bk) blocks, (K, J) for Y at
      (bk, bn) blocks).  When given, the operand is NOT re-profiled: the
      densities are planned from (if ``codes`` is None) and returned as the
      result's ``dens_x``/``dens_y`` side outputs verbatim.  The fused
      whole-model executor passes densities pooled from the producing
      kernel's writeback profile here (``profiler.BlockProfile``), so layer
      l+1's plan depends only on layer l's profile -- never on the
      materialized operand.

    Epilogue (fused at writeback, matching ``KernelIR``):
    ``out += residual * epilogue_scale`` then ``activation``
    (none/relu/prelu/elu).  ``out_density``/``out_counts`` profile the final
    result at ``out_block`` granularity (defaults to (bm, bn)) for planning
    the next kernel while this one executes.

    Execution computes ``out[i, j] = sum_k [codes[i, j, k] != SKIP] *
    X[i, k] @ Y[k, j]``, each product at default dot precision with float32
    accumulation.  With ``use_kernels=False`` (the XLA path) that sum is one
    batched block contraction: the lhs blocks of SKIP steps are selected to
    zero and every (i, j, k) product runs in a single ``dot_general``.  The
    masked lhs holds J copies of X.  A skipped product adds exactly zero
    whatever its lhs block holds; one whose rhs block holds inf or NaN adds
    NaN (0 * inf), where the loop below adds nothing.  ``use_kernels=True``
    instead runs a ``lax.scan`` over the I*J output tasks with a
    ``fori_loop`` over K and a ``lax.switch`` per step that routes
    GEMM/SpDMM/SPMM to the Pallas block-sparse kernels
    (``repro.kernels.ops``) tiled ``tile``/``unroll`` -- tile-granularity
    zero skipping on top of the block-granularity SKIP; interpret mode
    off-TPU.  Planned codes never change the result, only the cost (see
    ``dynasparse_dense_equivalent``).

    Format-aware execution (DESIGN.md section 13): with
    ``format_aware=True`` the planner additionally scores the row-CSR
    format via ``analyzer.plan_format`` (or accepts a precomputed ``fmt``
    code, the format analogue of the ``codes`` bypass, plus an optional
    pre-converted ``ell`` view so the fused walk can share one D2S across
    kernels).  When CSR wins AND every row fits ``csr_rmax`` (checked at
    runtime -- the decision is a prediction, the fit is a fact), the whole
    block path is replaced by one row-gather SPMM over the on-the-fly
    converted lhs under a ``lax.cond``; the epilogue and writeback profiling
    are shared, so side outputs keep their meaning.  The primitive ``codes``
    are still planned and returned either way (they are the side-output
    contract and the fallback path).  ``format_aware=False``, a static
    strategy, a non-Aggregate kernel, or a cost model without format costs
    all leave the trace byte-identical to the block-only executor.
    """
    m, n = x.shape[0], y.shape[1]
    bm, bk, bn = block
    xb = formats.tile_view(x, (bm, bk))      # (I, K, bm, bk)
    yb = formats.tile_view(y, (bk, bn))      # (K, J, bk, bn)
    I, K = xb.shape[:2]
    J = yb.shape[1]

    if dens_x is None:
        dens_x = _blocked_density(xb, x.shape[0], x.shape[1])   # (I, K)
    if dens_y is None:
        dens_y = _blocked_density(yb, y.shape[0], y.shape[1])   # (K, J)
    with jax.named_scope("k2p_plan"):
        if codes is None:
            codes = analyzer.plan_codes(strategy, dens_x, dens_y, cost_model,
                                        kernel_type=kernel_type)
        if format_aware and fmt is None:
            fmt = analyzer.plan_format(strategy, dens_x, dens_y, x.shape, n,
                                       block, cost_model,
                                       kernel_type=kernel_type, rmax=csr_rmax)

    out_dtype = jnp.promote_types(x.dtype, y.dtype)
    if residual is not None:
        out_dtype = jnp.promote_types(out_dtype, residual.dtype)

    def _contract():
        # out[i, j] = sum_k [codes[i, j, k] != SKIP] * X[i, k] @ Y[k, j].
        # ``where`` rather than a multiply, so a non-finite value in a
        # skipped lhs block cannot leak into the sum.  The mask is spread to
        # elements on the row-major operands, so XLA fuses it into one
        # batched matmul.  Blocked (I, K, bm, bk) operands are relaid out
        # into the TPU's (8, 128) tiles and back, and their 5-D contraction
        # compiles to convolutions whose dimension order differs between
        # the single-inference and the wave programs, breaking bitwise
        # equality.
        keep = (codes != Primitive.SKIP).transpose(1, 0, 2)  # (J, I, K)
        mask = jnp.repeat(jnp.repeat(keep, bm, axis=1), bk, axis=2)
        xm = jnp.where(mask, formats.pad_to_tiles(x, (bm, bk))[None], 0)
        ym = formats.pad_to_tiles(y, (bk, bn)).reshape(K * bk, J, bn)
        o = jnp.einsum("jmk,kjn->jmn", xm, ym,
                       preferred_element_type=jnp.float32)  # (J, M, bn)
        return o.transpose(1, 0, 2).reshape(I * bm, J * bn)

    def _gemm(acc, xk, yk):
        return acc + ops.gemm(xk, yk, tile=(tile[0], tile[1], tile[1])
                              ).astype(jnp.float32)

    def _spdmm(acc, xk, yk):
        return acc + ops.spdmm(xk, yk, tile=tile, bn=tile[1]
                               ).astype(jnp.float32)

    def _spmm(acc, xk, yk):
        return acc + ops.spmm(xk, yk, tile=tile).astype(jnp.float32)

    branches = (lambda acc, xk, yk: acc, _gemm, _spdmm, _spmm)

    def task(_, ij):
        i, j = ij // J, ij % J
        xrow = jax.lax.dynamic_index_in_dim(xb, i, 0, keepdims=False)
        ycol = jax.lax.dynamic_index_in_dim(yb, j, 1, keepdims=False)
        code_ij = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(codes, i, 0, False), j, 0, False)

        def red(k, acc):
            xk = jax.lax.dynamic_index_in_dim(xrow, k, 0, False)
            yk = jax.lax.dynamic_index_in_dim(ycol, k, 0, False)
            return jax.lax.switch(code_ij[k], branches, acc, xk, yk)

        acc = jax.lax.fori_loop(
            0, K, red, jnp.zeros((bm, bn), jnp.float32), unroll=unroll)
        return None, acc

    def _block_path():
        if use_kernels:
            _, blocks = jax.lax.scan(task, None, jnp.arange(I * J))
            o = blocks.reshape(I, J, bm, bn).transpose(0, 2, 1, 3)
            o = o.reshape(I * bm, J * bn)
        else:
            o = _contract()
        return o.astype(out_dtype)[:m, :n]

    if format_aware and fmt is not None:
        # On-the-fly D2S + row-gather SPMM, under a cond so a DENSE decision
        # runs the block path untouched.  The runtime ``fits`` check makes
        # the conversion lossless-or-ignored: if any row overflows csr_rmax
        # (the planner's fill-slack guess was wrong), fall back to blocks.
        if ell is None:
            with jax.named_scope("d2s"):
                ell = ell_when(fmt, x, csr_rmax)
        fits = jnp.max(ell.row_counts) <= csr_rmax
        use_csr = jnp.logical_and(fmt == Format.CSR, fits)

        def _csr_path():
            if use_kernels:
                o = ops.csr_spmm(ell, y, bn=tile[1])
            else:
                o = formats.ell_matmul(ell, y)
            return o.astype(out_dtype)

        out = jax.lax.cond(use_csr, _csr_path,
                           lambda: _block_path().astype(out_dtype))
        executed_fmt = use_csr.astype(jnp.int32)
    else:
        out = _block_path()
        executed_fmt = jnp.zeros((), jnp.int32)

    # --- fused epilogue (the FPGA applies these on the writeback path) ---
    if residual is not None:
        out = out + (residual if epilogue_scale == 1.0
                     else residual * epilogue_scale)
    out = _activate(out, activation)

    # --- Sparsity Profiler fused at writeback (Section V-B2) ---
    ob = out_block or (bm, bn)
    with jax.named_scope("writeback_profile"):
        out_counts = profiler.block_counts(out, ob)
        out_density = profiler.density_from_counts(out_counts, m, n, *ob)
    return DynasparseResult(out.astype(out_dtype), codes, dens_x, dens_y,
                            out_density, out_counts, executed_fmt)


def dynasparse_dense_equivalent(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Oracle: the dispatch NEVER changes the value, only the cost."""
    return jnp.dot(x.astype(jnp.float32), y.astype(jnp.float32)).astype(
        jnp.promote_types(x.dtype, y.dtype))


@functools.partial(
    jax.jit, static_argnames=("slope", "threshold", "out_block"))
def attention_adjacency(
    a: jnp.ndarray,
    z: jnp.ndarray,
    att_src: jnp.ndarray,
    att_dst: jnp.ndarray,
    *,
    slope: float = 0.2,
    threshold: float = 0.0,
    out_block: Tuple[int, int] = (128, 128),
) -> DynasparseResult:
    """Masked edge softmax over the adjacency support (GAT), thresholded.

    The one attention implementation (DESIGN.md §17), inlined by the
    executor's single-inference and wave programs alike.

    * ``a`` is the (n, n) normalized adjacency; only its nonzero SUPPORT
      matters (scores are computed fresh, the mask restricts softmax to
      edges + self loops).  All-zero rows -- bucket padding vertices, or
      dummy wave slots whose whole adjacency is zero -- produce exactly
      zero output rows, so padding profiles to density 0 and plans to
      SKIP downstream, same as every other kernel.
    * ``z = H @ W_h`` is the head's (n, f) transformed features;
      ``att_src``/``att_dst`` are its (f, 1) attention vectors:
      ``score_ij = LeakyReLU(att_src . z_i + att_dst . z_j, slope)``.
    * after the numerically-stable masked softmax, weights ``<= threshold``
      are dropped to exactly zero.  Rows sum to 1 before thresholding, so
      a head whose attention concentrates keeps few edges and a diffuse
      head keeps many -- per-head, per-input operand density, the thing
      the K2P planner cannot know until runtime.  ``threshold=0`` (the
      published model) drops only weights that are exactly zero, so the
      softmax is exact.

    Returns a :class:`DynasparseResult` so the side-output plumbing
    (writeback counts chained into the consumer's planner, report
    bookkeeping) is shared with the matmul kernels.  ``codes`` is the
    degenerate one-dense-task grid -- attention is not a blocked matmul;
    its cost is modeled as a single dense task -- and the interesting
    planning happens downstream, where the consumer Aggregate plans
    per-block primitives from THIS kernel's writeback profile.
    """
    m = a.shape[0]
    out_dtype = jnp.promote_types(a.dtype, z.dtype)
    support = a != 0
    # barrier: scores must be computed against the MATERIALIZED z.  Without
    # it, the whole-model program (where z's producing Update matmul is in
    # the same trace) may reassociate/refuse the projection against z's
    # producer -- fewer FLOPs, different rounding -- and the single-
    # inference and wave programs stop being bitwise equal.  The two (f,)
    # projections are one stacked (n, f) x (f, 2) dot for the same reason:
    # a single-column dot gets rewritten to a context-dependent reduction,
    # the 2-column one compiles to the same stable contraction in both
    # programs.
    zf = jax.lax.optimization_barrier(z.astype(jnp.float32))
    att = jnp.concatenate([att_src, att_dst], axis=1).astype(jnp.float32)
    s = jnp.dot(zf, att, preferred_element_type=jnp.float32)  # (n, 2)
    scores = s[:, :1] + s[:, 1:2].T
    scores = jnp.where(scores >= 0, scores, slope * scores)
    # stable masked softmax; empty rows (no support) resolve to all-zero
    # instead of NaN: their max is substituted with 0 and every entry is
    # masked out of the numerator, so 0 / 1 = 0.
    row_max = jnp.max(jnp.where(support, scores, -jnp.inf),
                      axis=1, keepdims=True)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    ex = jnp.where(support, jnp.exp(scores - row_max), 0.0)
    denom = jnp.maximum(jnp.sum(ex, axis=1, keepdims=True), 1e-30)
    alpha = ex / denom
    alpha = jnp.where(alpha > threshold, alpha, 0.0).astype(out_dtype)

    with jax.named_scope("writeback_profile"):
        out_counts = profiler.block_counts(alpha, out_block)
        out_density = profiler.density_from_counts(out_counts, m, m,
                                                   *out_block)
    one = jnp.ones((1, 1), jnp.float32)
    codes = jnp.full((1, 1, 1), Primitive.GEMM, jnp.int32)
    return DynasparseResult(alpha, codes, one, one, out_density, out_counts,
                            jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("mean", "activation",
                                             "out_block"))
def head_combine(
    heads: Tuple[jnp.ndarray, ...],
    residual: Optional[jnp.ndarray] = None,
    *,
    mean: bool,
    activation: str = "none",
    out_block: Tuple[int, int] = (128, 128),
) -> DynasparseResult:
    """One GAT layer's heads combined as published (DESIGN.md §17).

    The heads' (n, f) Aggregate outputs are concatenated in head order to
    (n, heads * f), or averaged when ``mean`` (the output layer); then the
    skip projection ``residual`` is added and ``activation`` applied, and
    the result is profiled at writeback like every kernel's, so the next
    layer plans from it.  ELU keeps zeros and signs, so the combined
    density is that of the sum, never thinned as ReLU thins it.

    Nothing is planned: ``codes`` is one SKIP step, as the combination
    performs no block product.
    """
    if mean:
        out = functools.reduce(jnp.add, heads[1:], heads[0]) / len(heads)
    else:
        out = jnp.concatenate(heads, axis=1)
    if residual is not None:
        out = out + residual
    out = _activate(out, activation)
    m, n = out.shape
    with jax.named_scope("writeback_profile"):
        out_counts = profiler.block_counts(out, out_block)
        out_density = profiler.density_from_counts(out_counts, m, n,
                                                   *out_block)
    one = jnp.ones((1, 1), jnp.float32)
    codes = jnp.full((1, 1, 1), Primitive.SKIP, jnp.int32)
    return DynasparseResult(out, codes, one, one, out_density, out_counts,
                            jnp.zeros((), jnp.int32))
