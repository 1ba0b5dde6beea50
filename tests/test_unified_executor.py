"""Unified executor: strategy parity, planner parity, cache, K2P reporting.

The PR contract for the plan/execute split (DESIGN.md section 1):

* value preservation: for every strategy the executor's output equals
  the dense oracle (``dynasparse_dense_equivalent`` applied kernel by
  kernel, epilogues included) to fp32 tolerance;
* planner parity: the histogram the executor reports (counted on the
  device from the traced planner's codes) matches what the host-side
  cost-model planner (``analyzer.plan_kernel_host`` -- the simulator's
  path) produces on the same profiled densities;
* one traced program per model: repeated shapes hit the program cache;
* K2P time: both the modeled soft-processor time and the measured host
  wall time are reported (the seed's ``* 0.0`` dead code is gone).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import analyzer, runtime
from repro.core.dynasparse import (dynasparse_dense_equivalent,
                                   dynasparse_matmul)
from repro.core.ir import Activation, KernelType
from repro.core.perf_model import FPGACostModel, Primitive
from repro.models import gnn as gnn_models

STRATEGIES = ("dynamic", "s1", "s2", "gemm")


def _dense_reference(compiled, tensors):
    """Oracle forward pass: plain dense matmuls + epilogues over the IR."""
    env = dict(tensors)
    for k in compiled.graph.topo_order():
        if k.kernel_type == KernelType.AGGREGATE:
            x = env[runtime._AGG_PRE[k.agg_op]]
        else:
            x = env[k.lhs]
        out = dynasparse_dense_equivalent(x, env[k.rhs])
        if k.epilogue_add is not None:
            out = out + env[k.epilogue_add] * k.epilogue_scale
        if k.activation_enabled:
            if k.activation == Activation.RELU:
                out = jax.nn.relu(out)
            elif k.activation == Activation.PRELU:
                out = jnp.where(out >= 0, out, 0.25 * out)
        env[k.out] = out
    return env[compiled.graph.kernels[-1].out]


@pytest.mark.parametrize("model", ["gcn", "sage", "gin", "sgc"])
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_matches_dense_equivalent(model, strategy):
    b = gnn_models.build_dense(model, "CO", scale=0.12, seed=2)
    out, rep = b.run(runtime.FusedModelExecutor(strategy=strategy))
    want = _dense_reference(b.compiled, b.tensors)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
    assert rep.total_cycles > 0


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_histogram_matches_host_planner(strategy):
    """Traced planner (inside the executor) == host planner (simulator path)
    on the same profiled densities, per kernel."""
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    eng = runtime.FusedModelExecutor(strategy=strategy)
    _, rep = b.run(eng)
    kernels = b.compiled.graph.topo_order()
    assert len(rep.kernels) == len(rep.counts) == len(kernels)
    for k, krep, counts in zip(kernels, rep.kernels, rep.counts):
        codes, _ = analyzer.plan_kernel_host(
            strategy, krep.dens_x, krep.dens_y, k.block_dims, eng.model,
            kernel_type=k.kernel_type)
        hist = np.bincount(codes.reshape(-1), minlength=4)
        np.testing.assert_array_equal(hist, krep.histogram, err_msg=k.name)
        np.testing.assert_array_equal(hist, counts, err_msg=k.name)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_matmul_strategy_value_parity(strategy):
    rng = np.random.default_rng(5)
    x = jnp.asarray((rng.normal(size=(80, 96))
                     * (rng.random((80, 96)) < 0.07)).astype(np.float32))
    y = jnp.asarray((rng.normal(size=(96, 48))
                     * (rng.random((96, 48)) < 0.5)).astype(np.float32))
    for ktype in (KernelType.AGGREGATE, KernelType.UPDATE):
        r = dynasparse_matmul(x, y, block=(16, 16, 16), strategy=strategy,
                              kernel_type=ktype)
        np.testing.assert_allclose(
            np.asarray(r.out),
            np.asarray(dynasparse_dense_equivalent(x, y)),
            atol=2e-4, rtol=2e-4)
        # static strategies never skip; dynamic skips the empty pairs
        if strategy != "dynamic":
            assert int(np.sum(np.asarray(r.codes) == Primitive.SKIP)) == 0


def test_fused_epilogue_and_out_density():
    rng = np.random.default_rng(6)
    x = jnp.asarray((rng.normal(size=(64, 64))
                     * (rng.random((64, 64)) < 0.2)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    r = dynasparse_matmul(x, y, block=(32, 32, 32), residual=res,
                          epilogue_scale=2.0, activation="relu",
                          out_block=(16, 16))
    want = jax.nn.relu(dynasparse_dense_equivalent(x, y) + 2.0 * res)
    np.testing.assert_allclose(np.asarray(r.out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # the writeback-fused profile describes the post-epilogue result
    want_dens = np.asarray(want != 0).reshape(4, 16, 2, 16).mean(axis=(1, 3))
    np.testing.assert_allclose(np.asarray(r.out_density), want_dens,
                               atol=1e-6)


def test_precomputed_codes_override_planner():
    rng = np.random.default_rng(7)
    x = jnp.asarray((rng.normal(size=(64, 64))
                     * (rng.random((64, 64)) < 0.1)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    planned = dynasparse_matmul(x, y, block=(32, 32, 32))
    forced = jnp.full_like(planned.codes, int(Primitive.GEMM))
    r = dynasparse_matmul(x, y, block=(32, 32, 32), codes=forced)
    np.testing.assert_array_equal(np.asarray(r.codes), np.asarray(forced))
    np.testing.assert_allclose(np.asarray(r.out), np.asarray(planned.out),
                               atol=2e-4, rtol=2e-4)


def test_executor_cache_hits_on_repeated_shapes():
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    eng = runtime.FusedModelExecutor()
    b.run(eng)
    assert eng.cache_misses == 1 and eng.cache_hits == 0
    b.run(eng)   # same shapes: the cached program re-launches
    b.run(eng)
    assert eng.cache_misses == 1 and eng.cache_hits == 2
    assert eng.trace_count == 1


def test_k2p_reports_modeled_and_measured():
    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    _, rep = b.run(runtime.FusedModelExecutor())
    for krep in rep.kernels:
        # modeled soft-processor time: linear in the decision count
        want = (krep.histogram.sum() * runtime._K2P_INSTRUCTIONS
                / runtime._SOFT_PROC_IPS)
        assert krep.k2p_seconds == pytest.approx(want)
        # measured host wall time is reported, not multiplied away
        assert krep.k2p_wall_seconds > 0.0
