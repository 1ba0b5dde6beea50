"""Batched GNN serving engine: parity, bucketing, order invariance.

The serving contract (DESIGN.md section 10):

* bitwise parity: for EVERY model of the zoo, ``GraphServeEngine.serve``
  returns per-request outputs bitwise equal to the naive per-request
  single-inference ``FusedModelExecutor.run`` on the same padded tensors
  -- wave batching, the scan, and dummy slot padding never touch a
  request's numerics;
* one jit trace per shape bucket: waves are padded to a fixed slot count,
  so repeated serving across any request mix re-traces only when a NEW
  bucket appears;
* request-order invariance: a request's output does not depend on its
  admission order or on which other requests share its wave.
"""
import numpy as np
import pytest

from repro.core import runtime
from repro.models import gnn as gnn_models
from repro.serving.graph_engine import (GraphRequest, GraphServeEngine,
                                        random_requests)

F_IN, HIDDEN, CLASSES = 32, 8, 6


def _engine(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("min_bucket", 32)
    return GraphServeEngine(model, f_in=F_IN, hidden=HIDDEN,
                            n_classes=CLASSES, **kw)


def _reqs(n=5, seed=1, sizes=(24, 60)):
    return random_requests(n, f_in=F_IN, sizes=sizes, seed=seed)


@pytest.mark.parametrize("model", gnn_models.GNN_MODELS)
def test_serve_matches_per_request_bitwise(model):
    """Whole zoo: served outputs == naive single-inference outputs, bit
    for bit, across mixed-size requests spanning two buckets (so waves mix
    real and dummy slots)."""
    eng = _engine(model)
    reqs = _reqs()
    served = eng.serve(reqs)
    naive = eng.run_naive(reqs)
    assert [r.request_id for r in served] == [r.request_id for r in naive]
    for s, n, req in zip(served, naive, reqs):
        assert s.logits.shape == (req.n_vertices, CLASSES)
        np.testing.assert_array_equal(
            s.logits, n.logits,
            err_msg=f"{model}: request {s.request_id} differs")


def test_one_trace_per_shape_bucket():
    """Admission pads every wave to ``slots``, so the batched program
    signature -- and hence the jit trace -- is unique per bucket."""
    eng = _engine("gcn")
    reqs = _reqs(7)                      # 2 buckets, multiple waves each
    eng.serve(reqs)
    assert len(eng.buckets) == 2
    assert eng.executor.trace_count == len(eng.buckets)
    assert eng.waves > len(eng.buckets)  # more waves than traces
    # steady state: same buckets, zero new traces, only program-cache hits
    hits0 = eng.executor.cache_hits
    eng.serve(_reqs(6, seed=9))
    assert eng.executor.trace_count == len(eng.buckets) == 2
    assert eng.executor.cache_hits > hits0
    # a NEW bucket (larger graph) traces exactly once more
    big = random_requests(1, f_in=F_IN, sizes=(150,), seed=3)
    eng.serve(big)
    assert len(eng.buckets) == 3
    assert eng.executor.trace_count == 3


def test_request_order_invariance():
    """Bitwise-identical per-request outputs regardless of admission
    order (different order => different wave composition, including which
    requests share a scan with which)."""
    reqs = _reqs(6, seed=4)
    eng = _engine("gcn")
    by_id = {r.request_id: r.logits for r in eng.serve(reqs)}
    for perm_seed in (0, 1):
        perm = np.random.default_rng(perm_seed).permutation(len(reqs))
        shuffled = [reqs[i] for i in perm]
        eng2 = _engine("gcn")
        for r in eng2.serve(shuffled):
            np.testing.assert_array_equal(
                r.logits, by_id[r.request_id],
                err_msg=f"request {r.request_id} depends on admission order")
    # solo admission (wave of one + dummies) matches too
    eng3 = _engine("gcn")
    for r in eng3.serve([reqs[2]]):
        np.testing.assert_array_equal(r.logits, by_id[r.request_id])


def test_results_in_request_order_and_sliced():
    eng = _engine("sage", slots=2)
    reqs = [GraphRequest(np.eye(n, dtype=np.float32),
                         np.ones((n, F_IN), np.float32), request_id=100 + i)
            for i, n in enumerate((20, 40, 17))]
    res = eng.serve(reqs)
    assert [r.request_id for r in res] == [100, 101, 102]
    assert [r.logits.shape[0] for r in res] == [20, 40, 17]
    assert res[0].bucket == 32 and res[1].bucket == 64


def test_shared_weight_profiles_cached_across_waves():
    """Steady-state waves never re-profile the shared weights on the
    host: the executor's identity-keyed input-profile cache holds one
    entry per (weight, granularity) no matter how many waves ran."""
    eng = _engine("gcn")
    eng.serve(_reqs(6, seed=2, sizes=(24,)))     # several waves, one bucket
    n_entries = len(eng.executor._input_profiles)
    assert n_entries > 0
    eng.serve(_reqs(6, seed=3, sizes=(24,)))
    assert len(eng.executor._input_profiles) == n_entries


def test_malformed_requests_rejected():
    eng = _engine("gcn")
    bad_width = GraphRequest(np.eye(8, dtype=np.float32),
                             np.ones((8, F_IN + 1), np.float32))
    with pytest.raises(ValueError, match="feature width"):
        eng.serve([bad_width])
    bad_adj = GraphRequest(np.eye(30, dtype=np.float32),
                           np.ones((20, F_IN), np.float32))
    with pytest.raises(ValueError, match="adjacency"):
        eng.serve([bad_adj])
    with pytest.raises(ValueError, match="adjacency"):
        eng.run_naive([bad_adj])


def _ok_request(n=8):
    return np.eye(n, dtype=np.float32), np.ones((n, F_IN), np.float32)


def test_nan_adjacency_rejected():
    """NaN adjacency must fail at admission -- it would otherwise flow
    through normalize_adjacency's degree sums and poison the whole wave."""
    eng = _engine("gcn")
    adj, feats = _ok_request()
    adj[2, 3] = np.nan
    with pytest.raises(ValueError, match="adjacency.*non-finite"):
        eng.serve([GraphRequest(adj, feats)])


def test_inf_adjacency_rejected():
    eng = _engine("gcn")
    adj, feats = _ok_request()
    adj[0, 1] = np.inf
    with pytest.raises(ValueError, match="adjacency.*non-finite"):
        eng.serve([GraphRequest(adj, feats)])


def test_nan_features_rejected():
    eng = _engine("gcn")
    adj, feats = _ok_request()
    feats[1, 1] = np.nan
    with pytest.raises(ValueError, match="features.*non-finite"):
        eng.serve([GraphRequest(adj, feats)])


def test_inf_features_rejected():
    eng = _engine("gcn")
    adj, feats = _ok_request()
    feats[0, 0] = -np.inf
    with pytest.raises(ValueError, match="features.*non-finite"):
        eng.run_naive([GraphRequest(adj, feats)])


def test_complex_dtype_rejected():
    eng = _engine("gcn")
    adj, feats = _ok_request()
    with pytest.raises(ValueError, match="features dtype"):
        eng.serve([GraphRequest(adj, feats.astype(np.complex64))])


def test_object_dtype_rejected():
    eng = _engine("gcn")
    adj, feats = _ok_request()
    with pytest.raises(ValueError, match="adjacency dtype"):
        eng.serve([GraphRequest(adj.astype(object), feats)])


def test_integer_and_bool_inputs_admitted():
    """int/bool graphs are legitimate adjacency encodings: they cast to
    float32 at padding and must NOT be rejected by the dtype gate."""
    eng = _engine("gcn")
    adj, feats = _ok_request()
    res = eng.serve([GraphRequest(adj.astype(bool), feats),
                     GraphRequest(adj.astype(np.int32), feats)])
    assert len(res) == 2 and res[0].logits.shape == (8, CLASSES)


def test_wave_report_plumbing():
    """dispatch_wave stamps the wave's width and real-slot count into the
    report (the continuous scheduler's EWMA reads the walls this plumbs)."""
    eng = _engine("gcn", slots=3)
    reqs = _reqs(2, sizes=(24,))
    out = eng.dispatch_wave(32, reqs)
    assert [r.request_id for r in out] == [r.request_id for r in reqs]
    rep = eng.last_wave_report
    assert rep is not None
    assert rep.wave_slots == 3 and rep.wave_real == 2
    assert eng.bucket_walls[32] == [rep.fused_wall_seconds]
    with pytest.raises(ValueError, match="wave of"):
        eng.dispatch_wave(32, [])
    with pytest.raises(ValueError, match="wave of"):
        eng.dispatch_wave(32, _reqs(4, sizes=(24,)))


def test_run_batch_report_modes():
    """The wave-level report: lean by default (no kernel bookkeeping, one
    wall clock), per-request per-kernel entries with collect_report=True,
    stacked planner codes with keep_codes=True."""
    reqs = _reqs(3, sizes=(24,))
    lean = _engine("gcn")
    lean.serve(reqs)
    assert lean.wave_walls and lean.wave_walls[0] > 0.0

    full = _engine("gcn", collect_report=True, keep_codes=True)
    full.serve(reqs)
    cm = full._compiled[full.buckets[0]]
    for out, codes in full.executor.planned_codes.items():
        assert codes.shape[0] == full.slots                 # stacked (B, ...)
    # a direct wave call returns per-request per-kernel bookkeeping
    bucket = full.buckets[0]
    batched = {name: np.stack([full._padded(r, bucket)[name] for r in reqs])
               for name in full._input_names[bucket]}
    _, rep = full.executor.run_batch(cm, full.weights, batched)
    n_kernels = len(cm.graph.kernels)
    assert len(rep.kernels) == len(reqs) * n_kernels
    assert rep.kernels[0].name.endswith("[0]")
    assert rep.kernels[-1].name.endswith(f"[{len(reqs) - 1}]")
    assert rep.fused_wall_seconds > 0.0
    # per-request parity of the planned codes vs a single inference
    per = runtime.FusedModelExecutor(strategy="dynamic", n_cc=full.n_cc,
                                     keep_codes=True)
    tensors = dict(full.weights)
    bucket = full.buckets[0]
    tensors.update({k: v for k, v in full._padded(reqs[0], bucket).items()
                    if k in full._input_names[bucket]})
    per.run(cm, tensors)
    for out, codes in per.planned_codes.items():
        np.testing.assert_array_equal(
            codes, full.executor.planned_codes[out][0],
            err_msg=f"{out}: slot-0 planner codes differ from per-request")


def test_run_naive_leaves_the_shared_weights_to_the_waves():
    """``run_naive`` never donates its inputs, which include the weights
    every wave reads: serving the same requests after it still reads
    them, and its results are bitwise the naive ones."""
    eng = _engine("gcn", donate=True)
    reqs = _reqs(4)
    naive = eng.run_naive(reqs)
    served = eng.serve(reqs)
    assert all(not w.is_deleted() for w in eng.weights.values())
    for s, n in zip(served, naive):
        np.testing.assert_array_equal(s.logits, n.logits,
                                      err_msg=f"request {s.request_id}")
