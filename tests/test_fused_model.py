"""Fused whole-model executor: parity, layer-overlap planning, trace count.

The PR contract for whole-model fusion (DESIGN.md section 9):

* kernel parity: for EVERY model of the example zoo under EVERY mapping
  strategy, each kernel of the whole-model program gives BITWISE the
  output of the same kernel run alone (``dynasparse_matmul``,
  ``attention_adjacency``, ``head_combine``) on the program's own
  materialized operands -- the dispatch and the density-profile chain
  never change the numerics;
* planner parity: the program plans each kernel from the producer's
  writeback profile (``out_counts`` pooled by ``BlockProfile.pool_rows``)
  with NO re-profiling, yet its code grids and operand densities are
  identical to those of the kernel run alone, which profiles its
  materialized operands -- i.e. the counts chain is exact, not an
  approximation;
* one jitted call per inference: a full-model run traces once; repeated
  runs re-launch the cached program without re-tracing;
* report parity: histograms, Alg. 8 makespans, and modeled K2P times
  equal a host recomputation from each row's own densities, and the
  report additionally models the overlapped (exposed) K2P time of
  Section V-B2.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import hw, spans
from repro.core import analyzer, profiler, runtime, scheduler
from repro.core.dynasparse import (attention_adjacency, dynasparse_matmul,
                                   head_combine)
from repro.core.ir import KernelType
from repro.core.perf_model import Primitive
from repro.models import gnn as gnn_models

STRATEGIES = ("dynamic", "s1", "s2", "gemm")


def _run(model, strategy, **kw):
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    fused = runtime.FusedModelExecutor(strategy=strategy, keep_codes=True,
                                       **kw)
    env, rep = fused.run(b.compiled, b.tensors)
    return b, fused, env, rep


def _alone(ex, k, flow, env):
    """Kernel ``k`` run alone on the materialized operands in ``env``,
    with the parameters the executor gives it (``flow``: its resolved
    operand flows, None for a head combination)."""
    n2 = k.scheme.n2
    act = k.activation.value if k.activation_enabled else "none"
    residual = env[k.epilogue_add] if k.epilogue_add is not None else None
    if k.kernel_type == KernelType.HEAD_COMBINE:
        return head_combine(tuple(env[h] for h in k.heads_in), residual,
                            mean=k.head_mean, activation=act,
                            out_block=(n2, n2))
    x, y = env[flow[0].source], env[k.rhs]
    if k.kernel_type == KernelType.ATTENTION:
        return attention_adjacency(
            x, y, env[k.att_src], env[k.att_dst], slope=k.att_slope,
            threshold=k.att_threshold, out_block=(n2, n2))
    return dynasparse_matmul(
        x, y, residual=residual, strategy=ex.strategy,
        kernel_type=k.kernel_type,
        epilogue_scale=k.epilogue_scale if residual is not None else 1.0,
        activation=act, out_block=(n2, n2), block=k.block_dims,
        cost_model=ex.model, format_aware=ex.format_aware,
        csr_rmax=ex.csr_rmax)


@pytest.mark.parametrize("model", gnn_models.GNN_MODELS)
def test_fused_matches_per_kernel_bitwise(model):
    """All four strategies: every kernel of the program is bitwise the
    kernel run alone on the program's materialized operands, with
    identical planner codes and operand densities, though the program
    never re-profiles an intermediate (it plans from the chained
    writeback counts)."""
    for strategy in STRATEGIES:
        b, fused, env, rep = _run(model, strategy, keep_intermediates=True)
        flows = fused._resolved_flows(b.compiled)
        kernels = b.compiled.graph.topo_order()
        for k, flow, row in zip(kernels, flows, rep.kernels):
            res = _alone(fused, k, flow, env)
            where = f"{model}/{strategy}/{k.out}"
            np.testing.assert_array_equal(
                np.asarray(res.out), np.asarray(env[k.out]),
                err_msg=f"{where}: outputs differ")
            np.testing.assert_array_equal(
                np.asarray(res.codes), fused.planned_codes[k.out],
                err_msg=f"{where}: planner codes differ")
            np.testing.assert_array_equal(np.asarray(res.dens_x), row.dens_x,
                                          err_msg=where)
            np.testing.assert_array_equal(np.asarray(res.dens_y), row.dens_y,
                                          err_msg=where)


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


# the codes of the kernels that plan nothing: attention is one dense task,
# the head combination one skipped step (``core.dynasparse``)
_FIXED = {KernelType.ATTENTION: Primitive.GEMM,
          KernelType.HEAD_COMBINE: Primitive.SKIP}


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_fused_report_matches_per_kernel(model, strategy):
    """The report -- histogram counted on the device, rows built on first
    read from the packed buffers -- equals, row by row, the histogram,
    Alg. 8 makespan and modeled K2P time recomputed on the host from the
    row's own densities."""
    b, fused, _, rep = _run(model, strategy)
    kernels = b.compiled.graph.topo_order()
    n_cc = b.compiled.partition.n_cc
    assert len(rep.kernels) == len(rep.counts) == len(kernels)
    makespans = []
    for k, row, counts in zip(kernels, rep.kernels, rep.counts):
        if k.kernel_type in _FIXED:
            codes = np.full((1, 1, 1), int(_FIXED[k.kernel_type]), np.int32)
            costs = analyzer.task_costs_host(codes, row.dens_x, row.dens_y,
                                             k.block_dims, fused.model)
        else:
            codes, costs = analyzer.plan_kernel_host(
                strategy, row.dens_x, row.dens_y, k.block_dims, fused.model,
                kernel_type=k.kernel_type)
        np.testing.assert_array_equal(codes, fused.planned_codes[k.out])
        hist = np.bincount(codes.reshape(-1), minlength=4)
        np.testing.assert_array_equal(row.histogram, hist)
        np.testing.assert_array_equal(row.histogram, counts)
        assert row.name == k.name and row.num_tasks == costs.size
        sched = scheduler.schedule_dynamic(costs.reshape(-1), n_cc)
        assert row.makespan_cycles == sched.makespan
        assert row.utilization == sched.utilization
        assert row.k2p_seconds == (codes.size * runtime._K2P_INSTRUCTIONS
                                   / runtime._SOFT_PROC_IPS)
        makespans.append(sched.makespan)
    np.testing.assert_array_equal(rep.histogram,
                                  np.sum([r.histogram for r in rep.kernels],
                                         axis=0))
    assert rep.histogram.dtype == np.int64
    assert rep.total_cycles == float(sum(makespans))
    assert rep.fused_wall_seconds is not None
    assert rep.wall_seconds == rep.fused_wall_seconds > 0.0


def _profile(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path), profiler_options=opts)


def test_report_is_one_readback_and_rows_wait_for_a_reader(tmp_path):
    """Each ``run`` copies one array to the host (``runtime.readbacks``);
    the per-kernel rows are built only when ``kernels`` is read
    (``runtime.report_rows``), which copies the packed buffers once."""
    b = gnn_models.build_dense("gat", "CO", scale=0.12, seed=2)
    fused = runtime.FusedModelExecutor()
    fused.run(b.compiled, b.tensors)
    with _profile(tmp_path):
        _, rep = fused.run(b.compiled, b.tensors)
        assert spans.snapshot()[1]["runtime.readbacks"] == 1
        fused.run(b.compiled, b.tensors)
        rep.histogram
        counters = spans.snapshot()[1]
        assert counters["runtime.readbacks"] == 2
        assert counters.get("runtime.report_rows", 0) == 0
        rep.kernels
        rep.total_cycles
        counters = spans.snapshot()[1]
        assert counters["runtime.report_rows"] == 1
        assert counters["runtime.readbacks"] == 3


def _side_leaves(model):
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    ex = runtime.FusedModelExecutor()
    fn, needed = ex._program(b.compiled, b.tensors)
    _, sides = jax.eval_shape(fn, b.tensors,
                              ex._input_counts(needed, b.tensors))
    return len(b.compiled.graph.kernels), len(jax.tree.leaves(sides))


def test_side_output_leaves_do_not_grow_with_the_kernel_count():
    (n_gcn, gcn), (n_gat, gat) = _side_leaves("gcn"), _side_leaves("gat")
    assert n_gat > n_gcn
    assert gcn == gat <= 4


@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_packed_fields_are_the_unpacked_side_outputs(model):
    """``planned_codes``, ``planned_formats`` and ``profiled_densities``,
    read from the packed buffers, are bitwise (values, dtype and shape)
    the side outputs of the same kernel walk returned unpacked."""
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    ex = runtime.FusedModelExecutor(keep_codes=True)
    ex.run(b.compiled, b.tensors)
    kernels = b.compiled.graph.topo_order()
    flows = ex._resolved_flows(b.compiled)
    needed = ex._needed_inputs(flows)

    def unpacked(tensors, in_counts):
        env = dict(tensors)
        profiles = {(name, blk): profiler.BlockProfile(
            counts, tuple(env[name].shape), blk)
            for (name, blk), counts in zip(needed, in_counts)}
        return ex._trace_kernels(kernels, flows, env, profiles)

    sides = jax.jit(unpacked)(b.tensors, ex._input_counts(needed, b.tensors))
    assert set(ex.planned_codes) == {k.out for k in kernels}
    for k, side in zip(kernels, sides):
        for got, want in ((ex.planned_codes[k.out], side[0]),
                          (ex.profiled_densities[k.out], side[3]),
                          (ex.planned_formats[k.out], side[4])):
            want = np.asarray(want)
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(got, want, err_msg=k.out)


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
    _, _, _, rep = _run("gcn", "dynamic")
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
