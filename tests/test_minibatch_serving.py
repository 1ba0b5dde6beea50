"""End-to-end mini-batch serving parity + hot-vertex cache semantics
(DESIGN.md §16).

The load-bearing invariant: mini-batch serving -- sampler, pinned store
gather, shape-bucketed waves, hot-vertex cache, coalescing -- is BITWISE
equal to the per-seed ``run_naive`` oracle (one single-inference
``FusedModelExecutor.run`` per sampled subgraph), across all four models,
arrival orders, and cache states.  Staleness: after a feature-store update no served result may
reflect pre-update features, and cache accounting must conserve.
"""
import functools

import numpy as np
import pytest

from conftest import HAVE_HYPOTHESIS, given, settings, st
from repro.data.sampling import powerlaw_host_graph
from repro.serving.graph_engine import GraphServeEngine
from repro.serving.minibatch import (FeatureStore, MiniBatchServeEngine,
                                     QueryTicket, VertexCache)
from repro.serving.scheduler import ContinuousGraphServer

N_V, F_IN, N_CLASSES = 400, 12, 5
FANOUTS = (3, 2)
MODELS = ["gcn", "sage", "gin", "sgc"]
QUERIES = [[7, 3], [3, 11, 7], [120], [11, 11, 55]]


@functools.lru_cache(maxsize=None)
def _host():
    g = powerlaw_host_graph(N_V, avg_degree=6, seed=0)
    feats = np.random.default_rng(7).standard_normal(
        (N_V, F_IN)).astype(np.float32)
    return g, feats


@functools.lru_cache(maxsize=None)
def _graph_engine(model):
    # shared per model so the compile cache amortizes across tests; its
    # counters drift but numerics are stateless
    return GraphServeEngine(model, f_in=F_IN, hidden=8,
                            n_classes=N_CLASSES, slots=4, min_bucket=32)


def _mb(model, *, cache_capacity=4096, store=None):
    g, feats = _host()
    if store is None:
        store = FeatureStore(feats.copy())   # tests may update in place
    return MiniBatchServeEngine(_graph_engine(model), g, store,
                                fanouts=FANOUTS,
                                cache_capacity=cache_capacity), store


# -- oracle parity ----------------------------------------------------------

@pytest.mark.parametrize("model", MODELS)
def test_oracle_parity_and_arrival_order(model):
    """serve_queries == per-seed run_naive oracle, bitwise -- and the
    answer for a vertex does not depend on which queries arrive around it
    or in what order (the per-seed sampling-seed contract)."""
    mb, _ = _mb(model)
    want = mb.oracle_queries(QUERIES)
    got = mb.serve_queries(QUERIES)
    assert [t.done for t in got] == [True] * len(QUERIES)
    for t, w in zip(got, want):
        np.testing.assert_array_equal(t.result(), w)
    # shuffled arrival, warm cache, different batching -- same bits
    order = [2, 0, 3, 1]
    again = mb.serve_queries([QUERIES[i] for i in order])
    for t, i in zip(again, order):
        np.testing.assert_array_equal(t.result(), want[i])


def test_cache_on_equals_cache_off():
    mb_on, _ = _mb("gcn")
    mb_off, _ = _mb("gcn", cache_capacity=None)
    assert mb_off.cache is None
    for _ in range(2):                       # 2nd pass: mb_on all-hits
        on = mb_on.serve_queries(QUERIES)
        off = mb_off.serve_queries(QUERIES)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a.result(), b.result())
    assert mb_on.cache.stats.hits > 0


def test_repeat_queries_hit_cache_bitwise():
    mb, _ = _mb("sage")
    first = mb.serve_queries(QUERIES)
    waves_before = mb.engine.waves
    second = mb.serve_queries(QUERIES)
    assert mb.engine.waves == waves_before   # nothing re-ran
    assert all(t.from_cache == len(dict.fromkeys(t.seeds)) for t in second)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.result(), b.result())
    rep = mb.report()
    assert rep["cache"]["hits"] > 0
    assert rep["cache"]["hit_rate"] > 0.0


# -- staleness: no result may reflect pre-update features -------------------

def test_store_update_invalidates_dependents():
    mb, store = _mb("gcn")
    pre = {t.seeds[0]: t.result()[0]
           for t in mb.serve_queries([[v] for v in (7, 3, 120)])}
    # bump vertex 7's OWN sampled neighborhood so its logits must move;
    # entries depending on any touched vertex get invalidated
    touched = mb.planner.sample(7).vertices
    store.update(touched, store.gather(touched) + 1.0)
    assert mb.cache.stats.invalidations >= 1
    assert mb.planner.lookup(7) is None      # the stale entry is gone
    post = mb.serve_queries([[7]])[0].result()[0]
    want = mb.oracle_queries([[7]])[0][0]
    np.testing.assert_array_equal(post, want)
    assert not np.array_equal(post, pre[7]), (
        "post-update serve returned the pre-update row")


def test_inflight_snapshot_is_delivered_but_not_cached():
    """A request that gathered before an update keeps its submission-time
    snapshot (delivered bitwise as-submitted) but must NOT populate the
    cache -- a later query recomputes under the new features."""
    mb, store = _mb("gin")
    planner = mb.planner
    req = planner.request_for(7)
    pre_snapshot = req.features.copy()       # gather -> version stamped
    store.update(np.array([7]), store.gather(np.array([7])) - 2.0)
    res = mb.engine.serve([req])[0]
    vertex, row = planner.complete(res)
    assert vertex == 7
    np.testing.assert_array_equal(req.features, pre_snapshot)
    assert planner.lookup(7) is None, "stale in-flight result was cached"
    fresh = mb.serve_queries([[7]])[0].result()[0]
    np.testing.assert_array_equal(fresh, mb.oracle_queries([[7]])[0][0])
    assert not np.array_equal(fresh, row)


def test_cache_accounting_conserves():
    mb, store = _mb("sgc")
    mb.serve_queries(QUERIES)
    mb.serve_queries(QUERIES)
    store.update(np.arange(N_V), store.gather(np.arange(N_V)) * 1.5)
    mb.serve_queries(QUERIES[:2])
    s = mb.cache.stats
    assert s.lookups == s.hits + s.misses
    assert s.insertions == (s.evictions + s.invalidations + len(mb.cache))


# -- VertexCache unit behavior (no engine) ----------------------------------

def test_vertex_cache_lru_eviction_and_reverse_index():
    c = VertexCache(capacity=2)
    r = {k: np.full(3, float(k), np.float32) for k in range(4)}
    c.put(("a",), r[0], deps=[0, 1])
    c.put(("b",), r[1], deps=[1, 2])
    assert c.get(("a",)) is not None         # "a" is now most-recent
    c.put(("c",), r[2], deps=[3])            # evicts LRU = "b"
    assert c.stats.evictions == 1
    assert c.get(("b",)) is None
    np.testing.assert_array_equal(c.get(("a",)), r[0])
    # "b"'s reverse-index entries must be gone: touching vertex 2
    # (only "b" depended on it) invalidates nothing
    assert c.invalidate([2]) == 0
    assert c.invalidate([1]) == 1            # kills "a"
    assert c.get(("a",)) is None
    s = c.stats
    assert s.lookups == s.hits + s.misses
    assert s.insertions == s.evictions + s.invalidations + len(c)
    with pytest.raises(ValueError):
        VertexCache(capacity=0)


def test_query_ticket_shed_rows_are_nan():
    qt = QueryTicket(0, [5, 9, 5])
    qt._pending = {5, 9}
    qt._fill(5, np.array([1.0, 2.0], np.float32))
    assert not qt.done
    qt.shed_seeds.append(9)
    qt._fill(9, None)                        # shed: explicitly absent
    assert qt.done
    out = qt.result()
    np.testing.assert_array_equal(out[0], [1.0, 2.0])
    assert np.isnan(out[1]).all()
    np.testing.assert_array_equal(out[2], out[0])   # duplicate seed shares


# -- per-wave gather plumbing -----------------------------------------------

def test_gather_seconds_surfaces_in_report(tmp_path):
    """The per-wave gather is the ``serve.fill`` span: under a profiler,
    one per wave, inside the same wave's ``serve.launch``."""
    import jax

    from repro import spans

    mb, _ = _mb("gcn")
    waves0 = mb.engine.waves
    with jax.profiler.trace(str(tmp_path)):
        mb.serve_queries([[3, 7, 11], [20]])
    recorded, _ = spans.snapshot()
    fills = [s for s in recorded if s.name == "serve.fill"]
    assert mb.engine.waves > waves0
    assert sorted(s.ids["wave"] for s in fills) == list(
        range(waves0, mb.engine.waves))
    for fill in fills:
        launch = recorded[fill.parent]
        assert launch.name == "serve.launch"
        assert launch.ids["wave"] == fill.ids["wave"]
        assert launch.start_ns <= fill.start_ns < fill.end_ns <= launch.end_ns
    assert "last_gather_seconds" not in mb.report()


# -- continuous front door --------------------------------------------------

def _drain_all(srv, tickets, rounds=50):
    for _ in range(rounds):
        srv.poll()
        srv.drain()
        if all(t.done for t in tickets):
            return
    raise AssertionError("queries never completed")


def test_submit_query_parity_coalescing_and_cache():
    mb, store = _mb("gcn")                   # reuse planner + oracle
    srv = ContinuousGraphServer(_graph_engine("gcn"),
                                minibatch=mb.planner)
    q1 = srv.submit_query([7, 3])
    q2 = srv.submit_query([3, 11, 7])        # 3 and 7 coalesce with q1
    assert mb.planner.inflight == 3          # unique vertices, not 5
    _drain_all(srv, [q1, q2])
    want = mb.oracle_queries([[7, 3], [3, 11, 7]])
    np.testing.assert_array_equal(q1.result(), want[0])
    np.testing.assert_array_equal(q2.result(), want[1])
    # hot vertices now cached: an identical query completes at submit
    q3 = srv.submit_query([7, 3, 11])
    assert q3.done and q3.from_cache == 3
    np.testing.assert_array_equal(q3.result(), want[1][[2, 0, 1]])
    assert srv.queries_submitted == 3
    # whole-graph traffic still routes alongside (non-query results pass
    # through poll/drain untouched)
    from repro.serving.graph_engine import GraphRequest
    sub = mb.planner.sample(55)
    req = GraphRequest(adjacency=sub.adjacency,
                       features=store.gather(sub.vertices), request_id=123)
    srv.submit(req)
    for _ in range(50):
        done = srv.poll() + srv.drain()
        if done:
            break
    assert [r.request_id for r in done] == [123]


def test_submit_query_requires_planner():
    srv = ContinuousGraphServer(_graph_engine("gcn"))
    with pytest.raises(ValueError):
        srv.submit_query([0])


def test_submit_query_version_checked_coalescing():
    """A query arriving after a store update must NOT join an in-flight
    request that gathered before it."""
    mb, store = _mb("sage")
    srv = ContinuousGraphServer(_graph_engine("sage"),
                                minibatch=mb.planner)
    q1 = srv.submit_query([7])
    rid1 = q1.tickets and mb.planner.inflight == 1
    assert rid1
    store.update(np.array([7]), store.gather(np.array([7])) + 3.0)
    q2 = srv.submit_query([7])               # fresh post-update request
    assert mb.planner.inflight == 2
    _drain_all(srv, [q1, q2])
    want = mb.oracle_queries([[7]])[0]       # post-update oracle
    np.testing.assert_array_equal(q2.result(), want)
    assert not np.array_equal(q1.result(), q2.result())
    # neither result was cached under a mismatched version... but q2's
    # gather matches the current version, so IT is cached
    assert mb.planner.lookup(7) is not None


# -- hypothesis driver (CI; container fallback relies on the sweeps) --------

if HAVE_HYPOTHESIS:

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 2**16), model=st.sampled_from(MODELS))
    def test_fuzzed_query_parity(seed, model):
        rng = np.random.default_rng(seed)
        queries = [rng.integers(0, N_V, size=rng.integers(1, 4)).tolist()
                   for _ in range(rng.integers(1, 4))]
        mb, _ = _mb(model)
        for t, w in zip(mb.serve_queries(queries),
                        mb.oracle_queries(queries)):
            np.testing.assert_array_equal(t.result(), w)
