"""Dynamic attention sparsity (GAT): the per-head, per-input operand
density the planner exploits (DESIGN.md §17).

What this file pins beyond the model sweeps (``test_fused_model`` /
``test_graph_serving`` parametrize over ``GNN_MODELS`` and already cover
GAT's kernel-alone and serving-vs-oracle bitwise parity):

* ``attention_adjacency`` semantics: masked softmax restricted to the
  adjacency support, rows sum to 1 pre-threshold, all-zero rows (bucket
  padding) stay exactly zero, thresholding drops weights to exact zero,
  and the writeback profile counts the POST-threshold support.
* per-head distinctness: two heads of the same layer, same input, produce
  DIFFERENT attention supports -- the fused walk profiles each head's
  writeback separately, so the per-head aggregates plan from per-head
  densities (the tentpole claim).
* sparsity drives the plan: raising the threshold sparsifies the
  attention operand and the dynamic K2P plan for the downstream
  aggregate changes with it (denser bands -> GEMM, sparser -> SpMM/SKIP).
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import compiler, runtime
from repro.core.dynasparse import attention_adjacency, head_combine
from repro.core.ir import Activation, KernelType
from repro.core.perf_model import Primitive
from repro.data import graphs as graph_data
from repro.models import gnn as gnn_models


def _gat_bundle(threshold=0.02, heads=2, seed=2):
    g = graph_data.materialize("CO", scale=0.12, seed=seed)
    spec = compiler.GNNModelSpec(
        "gat", [g.spec.f_in, g.spec.hidden, g.spec.n_classes],
        activation=Activation.ELU, gat_heads=(heads, heads),
        att_threshold=threshold)
    meta = compiler.GraphMeta("CO", g.spec.n_vertices, g.spec.n_edges,
                              g.spec.f_in)
    tensors = {"A": jnp.asarray(g.a_gcn), "A_mean": jnp.asarray(g.a_mean),
               "H0": jnp.asarray(g.h0)}
    cm = compiler.compile_model(spec, meta, n_cc=7, tensors=tensors,
                                align=16, on_chip_bytes=256 * 1024)
    for name, w in gnn_models.init_weights(cm, seed=seed).items():
        tensors[name] = jnp.asarray(w)
    return cm, tensors


# -- attention_adjacency unit semantics -------------------------------------

def test_attention_softmax_support_and_padding():
    rng = np.random.default_rng(0)
    n, f = 40, 8
    a = (rng.random((n, n)) < 0.2).astype(np.float32)
    a[-5:] = 0.0                              # bucket-padding rows
    z = rng.normal(size=(n, f)).astype(np.float32)
    asrc = rng.normal(size=(f, 1)).astype(np.float32)
    adst = rng.normal(size=(f, 1)).astype(np.float32)
    res = attention_adjacency(jnp.asarray(a), jnp.asarray(z),
                              jnp.asarray(asrc), jnp.asarray(adst),
                              threshold=0.0, out_block=(16, 16))
    alpha = np.asarray(res.out)
    assert alpha.shape == (n, n)
    # weights live ONLY on the support; un-thresholded rows sum to 1
    assert (alpha[a == 0] == 0.0).all()
    live = a[:-5].sum(axis=1) > 0
    np.testing.assert_allclose(alpha[:-5][live].sum(axis=1), 1.0, atol=1e-5)
    # padding rows are exactly zero -> density 0 -> SKIP downstream
    assert (alpha[-5:] == 0.0).all()
    # the writeback profile counts the actual output support
    from repro.core import profiler
    np.testing.assert_array_equal(
        np.asarray(res.out_counts),
        np.asarray(profiler.block_counts(res.out, (16, 16))))


def test_attention_threshold_drops_to_exact_zero():
    rng = np.random.default_rng(1)
    n, f = 32, 6
    a = (rng.random((n, n)) < 0.5).astype(np.float32)
    z = rng.normal(size=(n, f)).astype(np.float32)
    asrc = rng.normal(size=(f, 1)).astype(np.float32)
    adst = rng.normal(size=(f, 1)).astype(np.float32)
    args = (jnp.asarray(a), jnp.asarray(z), jnp.asarray(asrc),
            jnp.asarray(adst))
    free = np.asarray(attention_adjacency(*args, threshold=0.0,
                                          out_block=(16, 16)).out)
    cut = np.asarray(attention_adjacency(*args, threshold=0.05,
                                         out_block=(16, 16)).out)
    kept = cut != 0
    assert kept.sum() < (free != 0).sum()     # something was dropped
    assert (cut[~kept] == 0.0).all()          # dropped -> exact zero
    np.testing.assert_array_equal(cut[kept], free[kept])  # kept untouched
    assert (free[kept] > 0.05).all()


# -- per-head distinctness through the fused walk ---------------------------

def test_per_head_attention_densities_differ():
    """Two heads, same layer, same input: independently-initialized
    attention vectors concentrate differently, so each head's thresholded
    support -- the operand the per-head aggregate plans from -- has a
    different density profile."""
    cm, tensors = _gat_bundle()
    fused = runtime.FusedModelExecutor(keep_codes=True,
                                       keep_intermediates=True)
    env, _ = fused.run(cm, tensors)
    d1 = np.asarray(fused.profiled_densities["T1h1"])
    d2 = np.asarray(fused.profiled_densities["T1h2"])
    assert d1.shape == d2.shape
    assert not np.array_equal(d1, d2), (
        "both heads produced identical density profiles")
    # attention sparsified the operand below the full support density
    support = (np.asarray(tensors["A"]) != 0).mean()
    assert np.asarray(env["T1h1"]).astype(bool).mean() < support
    # the per-head aggregates were planned (per-head code grids exist and
    # the two heads' plans are per-head, not shared)
    assert "G1h1" in fused.planned_codes and "G1h2" in fused.planned_codes
    assert fused.planned_codes["G1h1"].shape == \
        fused.planned_codes["G1h2"].shape


def test_attention_sparsity_drives_the_plan():
    """Same graph, same weights, higher threshold -> sparser attention
    operand -> the dynamic plan for the head's aggregate moves toward
    SKIP/sparse primitives.  This is the paper's dynamic-sparsity loop
    closed over an INPUT-dependent operand."""
    codes = {}
    nnz = {}
    for threshold in (0.0, 0.6):
        cm, tensors = _gat_bundle(threshold=threshold, heads=1)
        eng = runtime.FusedModelExecutor(keep_codes=True,
                                         keep_intermediates=True)
        env, _ = eng.run(cm, tensors)
        codes[threshold] = eng.planned_codes["G1h1"]  # head 1's aggregate
        nnz[threshold] = int(np.asarray(env["T1h1"]).astype(bool).sum())
    assert nnz[0.6] < nnz[0.0]
    assert not np.array_equal(codes[0.6], codes[0.0]), (
        "plan did not react to attention sparsity")
    skips = {t: int((c == int(Primitive.SKIP)).sum())
             for t, c in codes.items()}
    assert skips[0.6] >= skips[0.0]


def test_gat_spec_knobs_change_signature():
    """att_threshold/att_slope are part of the executor cache signature:
    two specs differing only there must not share a cached program."""
    cm_a, _ = _gat_bundle(threshold=0.02)
    cm_b, _ = _gat_bundle(threshold=0.3)
    ks_a = [k for k in cm_a.graph.kernels if k.att_src is not None]
    ks_b = [k for k in cm_b.graph.kernels if k.att_src is not None]
    assert ks_a and len(ks_a) == len(ks_b)
    assert all(k.att_threshold == 0.02 for k in ks_a)
    assert all(k.att_threshold == 0.3 for k in ks_b)
    sig_a = runtime.FusedModelExecutor()._signature(cm_a, {})
    sig_b = runtime.FusedModelExecutor()._signature(cm_b, {})
    assert sig_a != sig_b


def test_build_sim_rejects_gat():
    with pytest.raises(NotImplementedError):
        gnn_models.build_sim("gat", "CO")
    spec = gnn_models.make_model_spec("gat", 16, 8, 4)
    assert dataclasses.asdict(spec)["model"] == "gat"


# -- published GAT: heads concatenated, averaged, ELU, skip ------------------

# reference gate, as a fraction of max |reference|: float32 on the CPU, the
# same gate as the other zoo models (test_fused_model.REF_TOL)
REF_TOL = 1e-4


def _published(threshold, n=150, f_in=20, seed=4):
    """Three layers: 2 heads x 8 concatenated, 2 heads x 8 concatenated
    plus the skip projection P2, 3 heads x 5 averaged."""
    rng = np.random.default_rng(seed)
    a = (rng.random((n, n)) < 0.04).astype(np.float32)
    a = np.maximum(a, a.T)
    a_gcn, a_mean = graph_data.normalize_adjacency(a)
    h0 = (rng.normal(size=(n, f_in)) ** 2).astype(np.float32)
    spec = compiler.GNNModelSpec(
        "gat", [f_in, 16, 16, 5], activation=Activation.ELU,
        gat_heads=(2, 2, 3), gat_skip=(2,), att_threshold=threshold)
    meta = compiler.GraphMeta("published", n, int(a.sum()) // 2, f_in)
    tensors = {"A": jnp.asarray(a_gcn), "A_mean": jnp.asarray(a_mean),
               "H0": jnp.asarray(h0)}
    cm = compiler.compile_model(spec, meta, n_cc=7, tensors=tensors,
                                align=16, on_chip_bytes=256 * 1024)
    for name, w in gnn_models.init_spec_weights(spec, seed=seed).items():
        tensors[name] = jnp.asarray(w)
    return spec, cm, tensors


def test_published_gat_ir_combines_heads_once_per_layer():
    spec, cm, _ = _published(0.0)
    kinds = [k.kernel_type for k in cm.graph.kernels]
    assert kinds.count(KernelType.ATTENTION) == 7
    assert kinds.count(KernelType.HEAD_COMBINE) == 3
    combine = [k for k in cm.graph.kernels
               if k.kernel_type == KernelType.HEAD_COMBINE]
    assert [k.heads_in for k in combine] == [
        ("G1h1", "G1h2"), ("G2h1", "G2h2"), ("G3h1", "G3h2", "G3h3")]
    assert [k.head_mean for k in combine] == [False, False, True]
    assert [k.epilogue_add for k in combine] == [None, "S2", None]
    assert [k.activation_enabled for k in combine] == [True, True, False]
    assert [k.out for k in combine] == ["H1", "H2", "H3"]
    w = gnn_models.init_spec_weights(spec)
    assert w["Wg1h1"].shape == (20, 8) and w["Wg3h1"].shape == (16, 5)
    assert w["P2"].shape == (16, 16) and "P1" not in w
    with pytest.raises(ValueError, match="head count per layer"):
        compiler.compile_model(
            dataclasses.replace(spec, gat_heads=(2, 2)),
            compiler.GraphMeta("x", 10, 10, 20), n_cc=7, align=16)


@pytest.mark.parametrize("threshold", [0.0, 0.05])
def test_published_gat_engines_match_the_reference(threshold):
    """The executor runs the published equations: within the float32 gate
    of ``reference_forward``, at the exact softmax and with a threshold
    that drops weights."""
    spec, cm, tensors = _published(threshold)
    fused = runtime.FusedModelExecutor(keep_intermediates=True)
    env_f, _ = fused.run(cm, tensors)
    ref = gnn_models.reference_forward(spec, tensors)
    assert env_f["H3"].shape == ref.shape == (150, 5)
    _, rel = gnn_models.reference_error(env_f["H3"], ref)
    assert rel <= REF_TOL, rel
    # the gate tells the published combination from the old summed heads
    # and from a forward without the skip projection
    no_skip = gnn_models.reference_forward(
        spec, {**tensors, "P2": jnp.zeros_like(tensors["P2"])})
    assert gnn_models.reference_error(no_skip, ref)[1] > 100 * REF_TOL
    kept = int(np.count_nonzero(np.asarray(env_f["T1h1"])))
    support = int(np.count_nonzero(np.asarray(tensors["A"])))
    assert (kept == support) if threshold == 0.0 else (kept < support)


def test_elu_writeback_profile_matches_a_reprofile():
    """The combined H{l}'s writeback profile, taken after ELU, is the
    profile of the tensor itself; ELU keeps zeros and signs, so its
    density is that of the concatenated heads, not thinned like ReLU's."""
    from repro.core import profiler

    _, cm, tensors = _published(0.0)
    fused = runtime.FusedModelExecutor(keep_intermediates=True)
    env, _ = fused.run(cm, tensors)
    n2 = cm.partition.n2
    for name in ("H1", "H2"):
        h = np.asarray(env[name])
        assert (h < 0).any() and (h > 0).any()    # ELU's negative branch
        np.testing.assert_array_equal(
            np.asarray(fused.profiled_densities[name]),
            np.asarray(profiler.BlockProfile.measure(
                env[name], (n2, n2)).densities()))
    heads = np.concatenate([np.asarray(env["G1h1"]),
                            np.asarray(env["G1h2"])], axis=1)
    np.testing.assert_array_equal(np.asarray(env["H1"]) != 0, heads != 0)


def test_head_combine_concatenates_in_order_and_averages():
    rng = np.random.default_rng(6)
    heads = tuple(jnp.asarray(rng.normal(size=(9, 3)).astype(np.float32))
                  for _ in range(3))
    skip = jnp.asarray(rng.normal(size=(9, 9)).astype(np.float32))
    cat = head_combine(heads, skip, mean=False, activation="elu",
                       out_block=(4, 4))
    want = np.concatenate([np.asarray(h) for h in heads], axis=1) + skip
    np.testing.assert_allclose(np.asarray(cat.out),
                               np.where(want > 0, want, np.expm1(want)),
                               rtol=1e-6, atol=1e-7)
    avg = head_combine(heads, None, mean=True, out_block=(4, 4))
    np.testing.assert_allclose(
        np.asarray(avg.out), sum(np.asarray(h) for h in heads) / 3,
        rtol=1e-6, atol=1e-7)
    assert int(np.asarray(avg.codes)[0, 0, 0]) == int(Primitive.SKIP)


def test_attention_counters_under_a_profiler(tmp_path):
    """``runtime.report`` counts, per inference and while a profiler runs,
    the attention kernels, their support and the entries kept."""
    import jax

    from repro import spans

    for threshold in (0.0, 0.05):
        _, cm, tensors = _published(threshold)
        fused = runtime.FusedModelExecutor()
        fused.run(cm, tensors)
        with jax.profiler.trace(str(tmp_path / str(threshold))):
            fused.run(cm, tensors)
            fused.run(cm, tensors)
        counters = spans.snapshot()[1]
        support = int(np.count_nonzero(np.asarray(tensors["A"])))
        assert counters["attention.heads"] == 2 * 7
        assert counters["attention.support"] == 2 * 7 * support
        kept = counters["attention.kept"]
        assert (kept == 2 * 7 * support) if threshold == 0.0 else (
            0 < kept < 2 * 7 * support)


def test_published_gat_wave_matches_the_reference_per_request():
    """The serving engine's wave program compiles the published graph
    (concatenated heads, skip projection, averaged output heads) and
    answers every request of a wave within the float32 gate."""
    spec, cm, tensors = _published(0.0)
    _, _, other = _published(0.0, seed=5)          # a second graph, same n
    flows = runtime.FusedModelExecutor._resolved_flows(cm)
    names = sorted({f.source for pair in flows for f in pair or ()
                    if f.producer is None and f.source.startswith(("A", "H"))})
    shared = {k: v for k, v in tensors.items() if k not in names}
    requests = [tensors, {**tensors, **{k: other[k] for k in names}}]
    batched = {k: jnp.stack([r[k] for r in requests]) for k in names}
    outs, _ = runtime.FusedModelExecutor().run_batch(cm, shared, batched)
    assert outs["H3"].shape == (2, 150, 5)
    for b, req in enumerate(requests):
        ref = gnn_models.reference_forward(spec, req)
        _, rel = gnn_models.reference_error(outs["H3"][b], ref)
        assert rel <= REF_TOL, (b, rel)
