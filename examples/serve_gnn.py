"""Batched GNN serving: a stream of graph queries through one engine.

Builds a :class:`~repro.serving.graph_engine.GraphServeEngine` (one weight
set, one compiled model + ONE jit trace per shape bucket), fires a
mixed-size synthetic query stream at it, and prints the admission picture:
which bucket each request landed in, per-wave dispatch walls, trace/cache
counters, throughput vs the naive per-request loop, and the bitwise parity
check against it.  The tail replays the SAME stream through the continuous
deadline-aware scheduler (`serving.scheduler`, DESIGN.md section 11):
Poisson arrivals, per-request deadlines, per-wave cut reasons, hit rate.
The last act doubles the arrival rate with ``shed="predicted-miss"``
admission control (DESIGN.md section 15): tickets carry the door
verdict, predicted losers are shed instead of served late, and the
per-class counters reconcile exactly.

The finale serves a MUTATING giant graph: mini-batch queries through a
sampler + pinned feature store, then a streaming edge delta
(``apply_delta``) that patches the block profile incrementally and
invalidates exactly the dependent cache entries (DESIGN.md section 17).

  PYTHONPATH=src python examples/serve_gnn.py [--model gat] [--n 12]
  PYTHONPATH=src python examples/serve_gnn.py --smoke   # CI: gate on parity
"""
import argparse
import sys
import time

import numpy as np

from repro.data.sampling import powerlaw_host_graph
from repro.jax_cache import configure_compile_cache
from repro.serving.graph_engine import GraphServeEngine, random_requests
from repro.serving.minibatch import FeatureStore, MiniBatchServeEngine
from repro.serving.scheduler import ContinuousGraphServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "sage", "gin", "sgc", "gat"])
    ap.add_argument("--n", type=int, default=12, help="requests")
    ap.add_argument("--slots", type=int, default=4, help="wave width")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small stream, exit nonzero unless every "
                         "parity check (batched/continuous/overload/"
                         "mini-batch) holds bitwise")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.slots = 6, 2
    parity = {}

    f_in = 64
    eng = GraphServeEngine(args.model, f_in=f_in, hidden=16, n_classes=7,
                           slots=args.slots)
    reqs = random_requests(args.n, f_in=f_in, sizes=(56, 100, 150), seed=0)
    print(f"== serving {args.n} {args.model.upper()} queries "
          f"(slots={args.slots}) ==")

    eng.serve(reqs)                       # warm: compile + trace per bucket
    t0 = time.perf_counter()
    results = eng.serve(reqs)             # steady state: cache hits only
    wall = time.perf_counter() - t0

    for r, q in zip(results, reqs):
        print(f"  req {r.request_id:2d}: |V|={q.n_vertices:4d} -> "
              f"bucket {r.bucket:4d}, wave {r.wave:2d}, "
              f"logits {r.logits.shape}")
    slots_run = eng.waves * eng.slots
    print(f"buckets={eng.buckets} waves={eng.waves} "
          f"traces={eng.executor.trace_count} "
          f"program-cache hit/miss="
          f"{eng.executor.cache_hits}/{eng.executor.cache_misses} "
          f"dummy-slot fill={1 - eng.served / slots_run:.0%}")
    # partial waves are padded with zero dummy slots (the price of one jit
    # trace per bucket, DESIGN.md section 10): sparse traffic with a high
    # fill fraction erodes the batching win; the bench's steadier stream
    # (benchmarks/bench_serving.py) is the representative number.
    print(f"steady-state: {wall * 1e3:.1f}ms total, "
          f"{args.n / wall:.1f} req/s, "
          f"wave walls p50={np.median(eng.wave_walls) * 1e3:.2f}ms")

    naive = eng.run_naive(reqs)           # warm the single-inference programs
    t0 = time.perf_counter()
    naive = eng.run_naive(reqs)
    naive_wall = time.perf_counter() - t0
    ok = parity["batched"] = all(np.array_equal(a.logits, b.logits)
                                 for a, b in zip(results, naive))
    print(f"naive per-request loop: {naive_wall * 1e3:.1f}ms "
          f"({args.n / naive_wall:.1f} req/s) -> "
          f"batched speedup {naive_wall / wall:.2f}x, bitwise==naive: {ok}")

    # -- continuous replay: same stream, but requests ARRIVE over time ----
    print(f"== continuous serving (Poisson arrivals, deadlines) ==")
    srv = ContinuousGraphServer(eng)      # engine already warm: all traces
    capacity = args.n / wall              # measured batch service rate
    budget = 2.0 * wall                   # per-request deadline budget
    rng = np.random.default_rng(1)
    arrivals = np.cumsum(rng.exponential(1.0 / (2.0 * capacity), args.n))
    t0 = time.monotonic()
    done, i = [], 0
    while i < args.n:
        now = time.monotonic()
        while i < args.n and t0 + arrivals[i] <= now:
            srv.submit(reqs[i], deadline=t0 + float(arrivals[i]) + budget)
            i += 1
        got = srv.poll()
        done += got
        if not got:
            time.sleep(1e-3)              # idle/not-cuttable: don't spin
    done += srv.drain()                   # end of stream: flush the tail
    span = max(r.completed_at for r in done) - t0
    hits = sum(bool(r.deadline_met) for r in done)
    for w in srv.dispatch_log:
        print(f"  wave: bucket {w.bucket:4d}, {w.n_real} real slot(s), "
              f"cut by {w.reason:8s}, wall {w.wall * 1e3:.2f}ms")
    naive_by_id = {r.request_id: r for r in naive}
    ok = parity["continuous"] = all(
        np.array_equal(r.logits, naive_by_id[r.request_id].logits)
        for r in done)
    print(f"continuous: {span * 1e3:.1f}ms stream span "
          f"({args.n / span:.1f} req/s), deadline hit-rate "
          f"{hits}/{args.n}, bitwise==naive: {ok}")

    # -- overload replay: 4x the arrival rate, admission control on ------
    print(f"== overload (4x arrivals, shed=\"predicted-miss\") ==")
    srv = ContinuousGraphServer(eng, shed="predicted-miss",
                                pressure_threshold=budget)
    arrivals = np.cumsum(rng.exponential(1.0 / (8.0 * capacity), args.n))
    t0 = time.monotonic()
    done, tickets, i = [], [], 0
    while i < args.n:
        now = time.monotonic()
        while i < args.n and t0 + arrivals[i] <= now:
            gold = i % 3 == 0             # every 3rd request is paid tier
            tickets.append(srv.submit(
                reqs[i], deadline=t0 + float(arrivals[i]) + budget,
                priority=1 if gold else 0, tenant="gold" if gold else "std"))
            i += 1
        got = srv.poll()
        done += got
        if not got:
            time.sleep(1e-3)
    done += srv.drain()
    hits = sum(bool(r.deadline_met) for r in done)
    ok = parity["overload"] = all(
        np.array_equal(r.logits, naive_by_id[r.request_id].logits)
        for r in done)
    for (tenant, prio), s in sorted(srv.class_stats.items()):
        print(f"  class {tenant}/p{prio}: admitted {s.admitted}, "
              f"shed {s.shed}, met {s.met}, missed {s.missed}")
    shed = [t for t in tickets if not t.admitted]
    print(f"overload: {len(done)} delivered ({hits} on deadline), "
          f"{len(srv.shed_log)} shed ({len(shed)} at the door), "
          f"peak pressure {srv.peak_pressure * 1e3:.1f}ms, "
          f"bitwise==naive: {ok}")

    # -- giant graph: mini-batch serving + streaming edge delta ----------
    print("== giant graph: mini-batch + streaming delta ==")
    n_giant = 1000 if args.smoke else 5000
    host = powerlaw_host_graph(n_giant, avg_degree=6, seed=0)
    store = FeatureStore(np.random.default_rng(2).standard_normal(
        (n_giant, f_in)).astype(np.float32))
    mb = MiniBatchServeEngine(eng, host, store, fanouts=(4, 3))
    queries = [[7, 3], [3, 11, 7]]
    got = mb.serve_queries(queries)
    want = mb.oracle_queries(queries)
    cold = all(np.array_equal(t.result(), w) for t, w in zip(got, want))
    # stream an edge delta touching vertex 7: the block profile is patched
    # in place (never re-profiled), only boundary-crossing cells replan,
    # and exactly the dependent cache entries are evicted
    absent = next(u for u in range(n_giant)
                  if u != 7 and u not in set(host.neighbors(7)))
    rep = mb.apply_delta([(7, absent)], [])
    print(f"  delta: +1 edge -> graph v{rep.graph_version}, "
          f"{rep.touched_cells}/{rep.total_cells} profile cells touched, "
          f"{rep.replan_cells} crossed a primitive boundary, "
          f"{rep.cache_invalidated} cache entries evicted")
    post = mb.serve_queries([[7]])[0].result()
    ok = parity["minibatch"] = bool(
        cold and np.array_equal(post, mb.oracle_queries([[7]])[0]))
    stats = mb.cache.stats
    print(f"  served {mb.planner.graph.n_edges} -edge graph: cache "
          f"hits={stats.hits} misses={stats.misses} "
          f"invalidations={stats.invalidations}, post-delta bitwise==oracle:"
          f" {ok}")

    if args.smoke:
        bad = sorted(k for k, v in parity.items() if not v)
        if bad:
            sys.exit(f"smoke parity failed: {bad}")
        print(f"smoke OK: {sorted(parity)} all bitwise")


if __name__ == "__main__":
    configure_compile_cache()
    main()
