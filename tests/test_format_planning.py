"""Format-aware K2P planning: pinned (primitive, format) decisions.

DESIGN.md section 13: the Analyzer's K2P decision is now a PAIR -- the
per-task primitive grid (``plan_codes``) plus one per-kernel ``Format``
code (``plan_format``).  These tests pin the decision table so a cost
model tweak that silently flips a planning regime fails loudly:

* the density sweep below fixes the (primitive, format) pair for every
  strategy on a canonical Aggregate shape;
* the format decision must charge Fig. 13's FULL transformation cost --
  so making the transform expensive tips CSR back to DENSE;
* the rmax fill guard vetoes CSR whenever the padded row format cannot
  hold the rows, regardless of the time comparison;
* format-aware execution keeps the executor's invariants: each kernel's
  executed format is the planner's decision on its directly profiled
  operands, the logits match the dense reference, and serving
  (``run_batch``) == naive bitwise, with CSR actually taken.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import analyzer
from repro.core.ir import KernelType
from repro.core.perf_model import (FPGACostModel, Format, Primitive,
                                   TPUCostModel)

M = K = 1024
BLOCK = (16, 16, 16)
RHS_COLS = 64
RMAX = 64
GRID = (M // 16, K // 16)


def _plan(a, model=None, *, strategy="dynamic", rmax=RMAX,
          kernel_type=KernelType.AGGREGATE):
    """Uniform-density Aggregate: A (M, K) at element density ``a`` times a
    dense feature matrix with RHS_COLS columns."""
    dx = jnp.full(GRID, a, jnp.float32)
    dy = jnp.ones((GRID[1], RHS_COLS // 16), jnp.float32)
    model = TPUCostModel() if model is None else model
    fmt = analyzer.plan_format(strategy, dx, dy, (M, K), RHS_COLS, BLOCK,
                               model, kernel_type=kernel_type, rmax=rmax)
    codes = analyzer.plan_codes(strategy, dx, dy, model,
                                kernel_type=kernel_type)
    prims = np.unique(np.asarray(codes)).tolist()
    return prims, (None if fmt is None else int(fmt))


# -- the pinned decision table ----------------------------------------------

@pytest.mark.parametrize("density,want_prims,want_fmt", [
    # empty lhs: every task SKIPs and there is nothing to transform
    (0.0,    [int(Primitive.SKIP)],  int(Format.DENSE)),
    # sparse regime: SpDMM blocks, but the row format amortizes better
    (0.0005, [int(Primitive.SPDMM)], int(Format.CSR)),
    (0.002,  [int(Primitive.SPDMM)], int(Format.CSR)),
    (0.01,   [int(Primitive.SPDMM)], int(Format.CSR)),
    # too dense for rmax rows: the fill guard keeps the block path
    (0.05,   [int(Primitive.SPDMM)], int(Format.DENSE)),
    (0.2,    [int(Primitive.SPDMM)], int(Format.DENSE)),
])
def test_dynamic_decision_sweep(density, want_prims, want_fmt):
    prims, fmt = _plan(density)
    assert prims == want_prims
    assert fmt == want_fmt


@pytest.mark.parametrize("strategy,agg_prim,upd_prim", [
    ("s1",   int(Primitive.SPDMM), int(Primitive.GEMM)),
    ("s2",   int(Primitive.SPDMM), int(Primitive.SPDMM)),
    ("gemm", int(Primitive.GEMM),  int(Primitive.GEMM)),
])
def test_static_strategies_never_plan_formats(strategy, agg_prim, upd_prim):
    """Static strategies keep their fixed primitive mapping and NEVER emit
    a format decision (plan_format is None => zero added trace)."""
    prims, fmt = _plan(0.01, strategy=strategy)
    assert prims == [agg_prim] and fmt is None
    prims_u, fmt_u = _plan(0.01, strategy=strategy,
                           kernel_type=KernelType.UPDATE)
    assert prims_u == [upd_prim] and fmt_u is None


def test_plan_format_gating():
    """The three other None gates: Update kernels, rmax <= 0, and a cost
    model without format costs (FPGA: block-vs-row is moot)."""
    assert _plan(0.01, kernel_type=KernelType.UPDATE)[1] is None
    assert _plan(0.01, rmax=0)[1] is None
    assert _plan(0.01, FPGACostModel())[1] is None


def test_transform_cost_tips_decision():
    """Fig. 13 accounting: the SAME density flips CSR -> DENSE once the
    on-the-fly transformation is made expensive enough."""
    assert _plan(0.002)[1] == int(Format.CSR)
    slow = dataclasses.replace(TPUCostModel(), eff_transform=1e-7)
    assert _plan(0.002, slow)[1] == int(Format.DENSE)


def test_transform_cost_scales_with_rmax():
    """Regression: ``transform_seconds`` must charge the ELL WRITE side by
    the ``rmax`` row budget (cols int32 + vals), not a dense (m, n)
    compacted buffer -- ``dense_to_ell`` never materialises one.  The cost
    is monotone in rmax and matches the read+write byte accounting."""
    m = TPUCostModel()
    walls = [float(m.transform_seconds(M, K, r)) for r in (16, 64, 512)]
    assert walls == sorted(walls) and walls[0] < walls[-1]
    want = ((M * K * m.dtype_bytes + M * RMAX * (4 + m.dtype_bytes))
            / (m.spec.hbm_bandwidth * m.eff_transform)
            + m.transform_overhead_s)
    assert float(m.transform_seconds(M, K, RMAX)) == pytest.approx(want)


@pytest.mark.parametrize("block_rows,want_fmt", [
    # the corrected tip-over: CSR amortizes once >= 6 of the 64 lhs
    # block-rows are occupied.  The old rmax-blind transform (a full
    # 2*m*n byte charge) put the tip-over at 11 block-rows, overpricing
    # the row path by the phantom (m, n) write
    (5, int(Format.DENSE)),
    (6, int(Format.CSR)),
    # 8 block-rows: DENSE under the old accounting -- the regression pin
    (8, int(Format.CSR)),
])
def test_rmax_aware_transform_tip_over(block_rows, want_fmt):
    dx = np.zeros(GRID, np.float32)
    dx[:block_rows, :] = 0.002
    dy = jnp.ones((GRID[1], RHS_COLS // 16), jnp.float32)
    fmt = analyzer.plan_format("dynamic", jnp.asarray(dx), dy, (M, K),
                               RHS_COLS, BLOCK, TPUCostModel(),
                               kernel_type=KernelType.AGGREGATE, rmax=RMAX)
    assert int(fmt) == want_fmt


def test_fill_guard_vetoes_csr():
    """At 5% density the time comparison still favors CSR (dropping the
    slack proves it) -- only the rmax fill guard keeps the block path."""
    assert _plan(0.05)[1] == int(Format.DENSE)
    no_guard = dataclasses.replace(TPUCostModel(), csr_fill_slack=0.0)
    assert _plan(0.05, no_guard)[1] == int(Format.CSR)


# -- execution invariants ---------------------------------------------------

F_IN, HIDDEN, CLASSES = 32, 8, 6

# transform made free so CSR is chosen even at test-sized graphs; the
# decision flows through the full engine stack exactly like at scale
CHEAP = dataclasses.replace(TPUCostModel(), eff_transform=1.0,
                            transform_overhead_s=0.0)


def test_fused_matches_per_kernel_with_formats():
    """Under format-aware planning every kernel executes the format
    ``analyzer.plan_format`` decides on its directly profiled operands
    (though the program plans from chained writeback counts), and the
    logits match the dense float32 reference."""
    from repro.core import profiler, runtime
    from repro.models import gnn as gnn_models

    b = gnn_models.build_dense("sage", "CO", scale=0.05, seed=2)
    fused = runtime.FusedModelExecutor(model=CHEAP, keep_codes=True,
                                       keep_intermediates=True)
    env, _ = fused.run(b.compiled, b.tensors)
    kernels = b.compiled.graph.topo_order()
    assert fused.planned_formats.keys() == {k.out for k in kernels}
    for k, (fx, _) in zip(kernels, fused._resolved_flows(b.compiled)):
        x, y = env[fx.source], env[k.rhs]
        bm, bk, bn = k.block_dims
        want = analyzer.plan_format(
            "dynamic", profiler.block_density(x, (bm, bk)),
            profiler.block_density(y, (bk, bn)), x.shape, y.shape[1],
            k.block_dims, CHEAP, kernel_type=k.kernel_type,
            rmax=fused.csr_rmax)
        want = Format.DENSE if want is None else int(want)
        assert int(fused.planned_formats[k.out]) == want, k.out
    # the aggregates of sage actually take the row-CSR path here
    assert any(int(np.asarray(f)) == int(Format.CSR)
               for f in fused.planned_formats.values())
    spec = gnn_models.make_model_spec(
        "sage", b.graph.spec.f_in, b.graph.spec.hidden,
        b.graph.spec.n_classes)
    ref = gnn_models.reference_forward(spec, b.tensors)
    _, rel = gnn_models.reference_error(env[kernels[-1].out], ref)
    assert rel <= 1e-4, rel      # the float32 gate of test_fused_model


def test_format_aware_default_engine_is_inert():
    """format_aware=True is the DEFAULT -- with the default FPGA cost model
    it must be bitwise inert (plan_format is None => identical trace)."""
    from repro.core import runtime
    from repro.models import gnn as gnn_models

    b = gnn_models.build_dense("gcn", "CO", scale=0.05, seed=1)
    on = runtime.FusedModelExecutor(format_aware=True)
    off = runtime.FusedModelExecutor(format_aware=False)
    env_on, _ = on.run(b.compiled, b.tensors)
    env_off, _ = off.run(b.compiled, b.tensors)
    last = b.compiled.graph.kernels[-1].out
    np.testing.assert_array_equal(np.asarray(env_on[last]),
                                  np.asarray(env_off[last]))


def test_serving_parity_and_trace_count_with_formats():
    """GraphServeEngine's bitwise serve == run_naive contract survives
    format-aware planning with CSR executing inside the batched scan, and
    the one-trace-per-bucket invariant still holds."""
    from repro.serving.graph_engine import GraphServeEngine, random_requests

    eng = GraphServeEngine("sage", f_in=F_IN, hidden=HIDDEN,
                           n_classes=CLASSES, slots=3, min_bucket=32,
                           cost_model=CHEAP, keep_codes=True)
    reqs = random_requests(5, f_in=F_IN, sizes=(24, 60), seed=1)
    served = eng.serve(reqs)
    naive = eng.run_naive(reqs)
    for s, n in zip(served, naive):
        np.testing.assert_array_equal(s.logits, n.logits,
                                      err_msg=f"request {s.request_id}")
    # the per-slot executed formats show the aggregates went CSR
    fmts = {k: np.asarray(v) for k, v in eng.executor.planned_formats.items()}
    assert all(np.all(fmts[k] == int(Format.CSR)) for k in ("N1", "N2")), fmts
    # one trace per bucket, and serving again re-traces nothing
    assert eng.executor.trace_count == len(eng.buckets)
    eng.serve(random_requests(4, f_in=F_IN, sizes=(24, 60), seed=2))
    assert eng.executor.trace_count == len(eng.buckets)
