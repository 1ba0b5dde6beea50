"""Partitioner (Alg 9), compiler/IR, scheduler (Alg 8), runtime engine."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import compiler, partitioner, runtime, scheduler
from repro.core.compiler import GNNModelSpec, GraphMeta
from repro.core.ir import AggOp, KernelType
from repro.models import gnn as gnn_models

# ---------------------------------------------------------------- Alg 9 --

def _graph(v=20000, f=512, hidden=128, classes=10):
    spec = GNNModelSpec("gcn", [f, hidden, classes])
    meta = GraphMeta("t", v, v * 10, f)
    return compiler.build_computation_graph(spec, meta), spec, meta


def test_partitioner_constraints():
    g, _, _ = _graph()
    for n_cc in (2, 7, 64):
        cfg = partitioner.choose_partition_sizes(g, n_cc=n_cc, align=16)
        partitioner.apply_partitioning(g, cfg)
        assert cfg.n2 <= cfg.n1 <= cfg.n_max
        for k in g.kernels:
            # Constraint 1: enough tasks for eta * N_CC load balance,
            # unless the kernel is just too small at minimum partition size.
            if k.workload >= cfg.eta * n_cc * 16 * 16:
                assert k.scheme.num_tasks >= cfg.eta * n_cc, (
                    k.name, k.scheme.num_tasks)


def test_partition_memory_cap():
    g, _, _ = _graph()
    small = 64 * 1024
    cfg = partitioner.choose_partition_sizes(g, n_cc=7, align=16,
                                             on_chip_bytes=small)
    n_max = partitioner.max_partition_size(small, align=16)
    assert cfg.n1 <= n_max and cfg.n2 <= n_max


# ------------------------------------------------------------- compiler --

@pytest.mark.parametrize("model,n_kernels", [
    ("gcn", 4), ("sage", 6), ("gin", 6), ("sgc", 3)])
def test_ir_structure(model, n_kernels):
    spec = GNNModelSpec(model, [64, 16, 7] if model != "sgc" else [64, 7])
    meta = GraphMeta("t", 1000, 5000, 64)
    g = compiler.build_computation_graph(spec, meta)
    assert len(g) == n_kernels
    edges = g.edges()
    assert len(edges) >= len(g) - 1  # connected chain at least
    # every Update kernel's dims match the spec chain
    for k in g.kernels:
        if k.kernel_type == KernelType.UPDATE:
            assert k.f_in in spec.layer_dims or model == "gin"


def test_compile_profiles_static_sparsity(rng):
    h0 = rng.normal(size=(300, 64)).astype(np.float32)
    h0 *= rng.random((300, 64)) < 0.1
    a = (rng.random((300, 300)) < 0.02).astype(np.float32)
    spec = GNNModelSpec("gcn", [64, 16, 7])
    meta = GraphMeta("t", 300, int(a.sum()), 64)
    cm = compiler.compile_model(spec, meta, n_cc=7, align=16,
                                tensors={"A": jnp.asarray(a),
                                         "H0": jnp.asarray(h0)})
    assert abs(cm.static_stats["H0"].density - 0.1) < 0.05
    assert cm.compile_seconds < 5.0  # Table IX: preprocessing is cheap


# ------------------------------------------------------------ scheduler --

def test_dynamic_beats_static_on_skewed_costs(rng):
    costs = rng.pareto(1.5, size=200) + 0.01
    dyn = scheduler.schedule_dynamic(costs, 7)
    stat = scheduler.schedule_static(costs, 7)
    lpt = scheduler.schedule_lpt(costs, 7)
    assert dyn.makespan <= stat.makespan + 1e-9
    assert lpt.makespan <= dyn.makespan + 1e-9
    # every task assigned exactly once
    for s in (dyn, stat, lpt):
        seen = sorted(t for a in s.assignment for t in a)
        assert seen == list(range(200))


def test_steal_rebalance_never_hurts(rng):
    costs = rng.pareto(1.2, size=97) + 0.01
    base = scheduler.schedule_static(costs, 5)
    fixed = scheduler.steal_rebalance(base, costs)
    assert fixed.makespan <= base.makespan + 1e-9
    seen = sorted(t for a in fixed.assignment for t in a)
    assert seen == list(range(97))


# ------------------------------------------------- engine vs dense ref ---

@pytest.mark.parametrize("model", ["gcn", "sage", "gin", "sgc"])
@pytest.mark.parametrize("strategy", ["dynamic", "s1", "s2", "gemm"])
def test_engine_matches_dense_reference(model, strategy):
    b = gnn_models.build_dense(model, "CO", scale=0.15, seed=1)
    out, rep = b.run(runtime.FusedModelExecutor(strategy=strategy))
    # dense oracle: run the same IR forcing GEMM everywhere
    want, _ = b.run(runtime.FusedModelExecutor(strategy="gemm"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
    assert rep.total_cycles > 0


def test_dynamic_mapping_dominates_static():
    """The paper's headline: dynamic K2P <= min(S1, S2) in predicted
    latency, per model/dataset (cost-model simulation)."""
    for model in ("gcn", "sage"):
        sim = gnn_models.build_sim(model, "CI")
        lat = {s: sim.simulate(s).total_cycles
               for s in ("dynamic", "s1", "s2")}
        assert lat["dynamic"] <= min(lat["s1"], lat["s2"]) * 1.02


def test_dynamic_skips_empty_partitions():
    sim = gnn_models.build_sim("gcn", "CI")
    rep = sim.simulate("dynamic")
    assert rep.histogram[0] > 0          # SKIP count (Alg 7 line 6)
    rep_s2 = sim.simulate("s2")
    assert rep_s2.histogram[0] == 0      # static mappings cannot skip


def test_runtime_overhead_modeled():
    """Fig 13 mechanism: K2P cost scales with the decision count (O(I*J*K)
    scalars, 'small overhead compared with the computation complexity of a
    task'), is absolutely tiny on the soft processor, and the per-kernel
    decisions for layer l+1 can overlap layer l's execution."""
    sim = gnn_models.build_sim("gcn", "PU")
    rep = sim.simulate("dynamic")
    assert 0 < rep.k2p_seconds < 0.05          # tens of ms at 500 MIPS
    per_kernel = [k.k2p_seconds for k in rep.kernels]
    decisions = [int(k.histogram.sum()) for k in rep.kernels]
    # linear in decisions
    ratios = [t / d for t, d in zip(per_kernel, decisions)]
    assert max(ratios) - min(ratios) < 1e-12


def test_pruning_increases_dynamic_advantage():
    """Table VIII trend: more weight sparsity => larger speedup vs S1."""
    so = []
    for dens in (1.0, 0.3, 0.05):
        sim = gnn_models.build_sim("gcn", "PU", weight_density=dens)
        dyn = sim.simulate("dynamic").total_cycles
        s1 = sim.simulate("s1").total_cycles
        so.append(s1 / dyn)
    assert so[0] < so[1] < so[2]
