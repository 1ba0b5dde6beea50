"""Fused whole-model executor: parity, layer-overlap planning, trace count.

The PR contract for whole-model fusion (DESIGN.md section 9):

* value parity: for EVERY model of the example zoo (GCN / GraphSAGE / GIN /
  SGC) under EVERY mapping strategy, the fused executor's output is
  BITWISE equal to the per-kernel engine's -- the dispatch and the
  density-profile chain never change the numerics;
* planner parity: the fused path plans each kernel from the producer's
  writeback profile (``out_counts`` pooled by ``BlockProfile.pool_rows``)
  with NO re-profiling, yet its code grids are identical to the per-kernel
  path's, which re-profiles every materialized operand -- i.e. the counts
  chain is exact, not an approximation;
* one jitted call per inference: a full-model run traces once; repeated
  runs re-launch the cached program without re-tracing;
* report parity: histograms, Alg. 8 makespans, and modeled K2P times agree
  between the executors, and the fused report additionally models the
  overlapped (exposed) K2P time of Section V-B2.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import hw
from repro.core import profiler, runtime
from repro.models import gnn as gnn_models

STRATEGIES = ("dynamic", "s1", "s2", "gemm")


def _run_both(model, strategy, **kw):
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    per = runtime.DynasparseEngine(strategy=strategy, keep_codes=True, **kw)
    env_p, rep_p = per.run(b.compiled, b.tensors)
    fused = runtime.FusedModelExecutor(strategy=strategy, keep_codes=True,
                                       **kw)
    env_f, rep_f = fused.run(b.compiled, b.tensors)
    return b, (per, env_p, rep_p), (fused, env_f, rep_f)


@pytest.mark.parametrize("model", gnn_models.GNN_MODELS)
def test_fused_matches_per_kernel_bitwise(model):
    """All four strategies: bitwise-equal outputs AND identical planner
    code sequences, though the fused path never re-profiles an
    intermediate (it plans from the chained writeback counts)."""
    for strategy in STRATEGIES:
        b, (per, env_p, _), (fused, env_f, _) = _run_both(model, strategy)
        last = b.compiled.graph.kernels[-1].out
        np.testing.assert_array_equal(
            np.asarray(env_p[last]), np.asarray(env_f[last]),
            err_msg=f"{model}/{strategy}: outputs differ")
        assert per.planned_codes.keys() == fused.planned_codes.keys()
        for out, codes in per.planned_codes.items():
            np.testing.assert_array_equal(
                codes, fused.planned_codes[out],
                err_msg=f"{model}/{strategy}/{out}: planner codes differ")


# reference gate, as a fraction of max |reference| (float32 on the CPU)
REF_TOL = 1e-4


@pytest.mark.parametrize("model", gnn_models.GNN_MODELS)
def test_fused_matches_float32_reference(model):
    """The fused executor's logits agree with the plain float32 forward
    (``gnn.reference_forward``), and the tolerance is tight enough that a
    forward which skips every aggregation (A := I) fails it by far."""
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    out, _ = b.run(runtime.FusedModelExecutor(strategy="dynamic"))
    spec = gnn_models.make_model_spec(
        model, b.graph.spec.f_in, b.graph.spec.hidden, b.graph.spec.n_classes)
    ref = gnn_models.reference_forward(spec, b.tensors)
    assert out.shape == ref.shape == (b.graph.spec.n_vertices,
                                      b.graph.spec.n_classes)
    _, rel = gnn_models.reference_error(out, ref)
    assert rel <= REF_TOL, (model, rel)
    eye = jnp.eye(ref.shape[0], dtype=jnp.float32)
    no_agg = gnn_models.reference_forward(
        spec, {**b.tensors, "A": eye, "A_mean": eye})
    _, rel_no_agg = gnn_models.reference_error(no_agg, ref)
    assert rel_no_agg > 100 * REF_TOL, (model, rel_no_agg)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_report_matches_per_kernel(strategy):
    _, (_, _, rep_p), (_, _, rep_f) = _run_both("gcn", strategy)
    for kp, kf in zip(rep_p.kernels, rep_f.kernels):
        np.testing.assert_array_equal(kp.histogram, kf.histogram)
        assert kp.makespan_cycles == kf.makespan_cycles
        assert kp.k2p_seconds == kf.k2p_seconds
        np.testing.assert_array_equal(kp.dens_x, kf.dens_x)
        np.testing.assert_array_equal(kp.dens_y, kf.dens_y)
    assert rep_f.fused_wall_seconds is not None
    assert rep_f.wall_seconds == rep_f.fused_wall_seconds > 0.0


def test_one_jitted_call_per_inference():
    """The fused path is ONE traced program: repeated runs (and repeated
    engines of the same model) hit the program cache, never re-trace."""
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    fused = runtime.FusedModelExecutor()
    fused.run(b.compiled, b.tensors)
    assert fused.trace_count == 1 and fused.cache_misses == 1
    fused.run(b.compiled, b.tensors)
    fused.run(b.compiled, b.tensors)
    assert fused.trace_count == 1          # no re-trace
    assert fused.cache_hits == 2 and fused.cache_misses == 1


def test_profile_chain_is_exact_on_ragged_blocks():
    """BlockProfile.pool_rows (integer-count sum) == direct profiling at
    the pooled granularity, including ragged edge blocks where the
    density-space mean-pool would NOT be exact."""
    rng = np.random.default_rng(3)
    x = jnp.asarray((rng.normal(size=(52, 24))
                     * (rng.random((52, 24)) < 0.3)).astype(np.float32))
    fine = profiler.BlockProfile.measure(x, (8, 8))      # 7 row blocks (ragged)
    pooled = fine.pool_rows(4)                           # -> (32, 8) blocks
    direct = profiler.BlockProfile.measure(x, (32, 8))
    np.testing.assert_array_equal(np.asarray(pooled.counts),
                                  np.asarray(direct.counts))
    np.testing.assert_array_equal(np.asarray(pooled.densities()),
                                  np.asarray(direct.densities()))


def test_operand_flows_wiring():
    """ir.OperandFlow metadata: intermediates chain from their producer at
    the right pool factor; graph inputs do not."""
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    g = b.compiled.graph
    n1, n2 = b.compiled.partition.n1, b.compiled.partition.n2
    produced = {}
    for i, (k, (fx, fy)) in enumerate(zip(g.topo_order(), g.operand_flows())):
        for f in (fx, fy):
            if f.source in produced:
                assert f.producer == produced[f.source]
                assert f.block[1] == n2
                assert f.pool_rows == f.block[0] // n2
            else:
                assert f.producer is None and f.pool_rows == 1
        produced[k.out] = i
    # a GCN layer chains features into an Aggregate at (N1, N2) granularity
    pooled = [f for pair in g.operand_flows() for f in pair
              if f.producer is not None and f.block[0] == n1]
    if n1 > n2:
        assert all(f.pool_rows == n1 // n2 for f in pooled)


def test_k2p_overlap_model():
    """Exposed (overlapped) K2P time: bounded by the serial sum, and no
    lower than the first kernel's un-hideable planning time."""
    _, (_, _, _), (_, _, rep) = _run_both("gcn", "dynamic")
    freq = hw.ALVEO_U250.freq_hz
    exposed = rep.k2p_exposed_seconds(freq)
    assert 0.0 < exposed <= rep.k2p_seconds
    assert exposed >= rep.kernels[0].k2p_seconds
    # huge accelerator throughput -> nothing hides: exposed == serial sum
    assert rep.k2p_exposed_seconds(float("inf")) == pytest.approx(
        rep.k2p_seconds)


def test_collect_report_false_skips_bookkeeping():
    """Serving knob: no per-kernel host bookkeeping (codes transfer, cost
    prediction, scheduling), same outputs, wall clock still reported."""
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    full = runtime.FusedModelExecutor()
    env_full, _ = full.run(b.compiled, b.tensors)
    lean = runtime.FusedModelExecutor(collect_report=False)
    env_lean, rep = lean.run(b.compiled, b.tensors)
    assert rep.kernels == [] and rep.histogram.sum() == 0
    assert rep.wall_seconds == rep.fused_wall_seconds > 0.0
    last = b.compiled.graph.kernels[-1].out
    np.testing.assert_array_equal(np.asarray(env_full[last]),
                                  np.asarray(env_lean[last]))


def test_fused_keep_intermediates_and_density_side_outputs():
    b = gnn_models.build_dense("sage", "CO", scale=0.12, seed=2)
    fused = runtime.FusedModelExecutor(keep_intermediates=True)
    env, _ = fused.run(b.compiled, b.tensors)
    for k in b.compiled.graph.topo_order():
        assert k.out in env
        assert k.out in fused.profiled_densities
        # the writeback profile describes the actual (post-epilogue) result
        n2 = b.compiled.partition.n2
        want = np.asarray(profiler.block_density(env[k.out], (n2, n2)))
        np.testing.assert_array_equal(
            np.asarray(fused.profiled_densities[k.out]), want)
