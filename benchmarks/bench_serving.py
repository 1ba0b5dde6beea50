"""Serving ladder: batched GraphServeEngine waves vs naive per-request loop.

Measures the whole request -> bucket -> profile -> plan -> execute pipeline
end to end over a mixed-size query stream (each request its own graph,
hence its own density profile):

* **naive** -- one single-inference ``FusedModelExecutor.run`` per
  request (``GraphServeEngine.run_naive``): same pad-to-bucket admission,
  but one dispatch per request, no batching;
* **served** -- ``GraphServeEngine.serve``: shape-bucketed admission waves
  through the batched fused program (one jitted dispatch per wave,
  profile-chained K2P planning, no per-request host bookkeeping);
* **continuous** -- ``serving.scheduler.ContinuousGraphServer`` over the
  same engine, fed by an ARRIVAL PROCESS: Poisson arrivals at
  ``--load`` x the engine's measured wave capacity, each request carrying
  an absolute deadline.  Measures per-request sojourn latency
  (arrival -> wave completion), deadline hit-rate, and throughput over the
  busy span, against the synchronous ``serve`` baseline on the SAME
  request set (DESIGN.md section 11).

Per engine: p50/p99 per-request latency (a served request's latency is its
wave's wall clock -- requests share the dispatch), aggregate throughput
(requests/s), and per-wave padding efficiency (real/slots occupancy from
``InferenceReport.wave_real``/``wave_slots``).  Timing is best-of-N with
the two paths interleaved per round, so slow drift in machine load cancels
instead of showing up as a speedup.
``BENCH_serving.json`` carries
the serving perf trajectory (sync rows + a continuous row per model);
``--smoke`` is the CI gate (bitwise served-vs-naive parity + a loose
throughput floor) and writes ``BENCH_serving.smoke.json`` for the workflow
artifact; ``--smoke --continuous`` additionally gates continuous-vs-naive
parity, the deadline hit-rate floor, and continuous throughput vs sync,
writing ``BENCH_serving.continuous.smoke.json`` alongside.

``--mesh`` is the multidevice ladder (DESIGN.md section 12): waves
device-sharded over a ``cores`` mesh of every visible device, single-lane
vs one-lane-per-device continuous dispatch on the same Poisson stream,
gating sharded-vs-naive parity, the per-(bucket, lane-count) trace bound,
and multi-lane >= ``--lane-tol`` x single-lane throughput.  CI's
multidevice job runs it on 8 emulated host devices and uploads
``BENCH_serving.multidevice.smoke.json``.

``--mesh --submesh`` is the disjoint-group ladder on top (DESIGN.md
section 14): the resize scheduler partitions the mesh into per-lane
device groups between waves (``plan_groups`` +
``begin_wave(submesh=...)``), gating resize-vs-naive parity, the
per-(bucket, group-size) trace bound, and submesh multi-lane >=
``--lane-tol`` x single-lane throughput; the row also carries the
shared-mesh lane speedup so submesh-vs-shared reads from one artifact
(smoke artifact ``BENCH_serving.submesh.smoke.json``, full runs merge
``submesh_rows`` into ``BENCH_serving.json``).

``--overload`` is the overload-control ladder (DESIGN.md section 15):
Poisson replays at 1x/3x/10x the measured capacity (1x/3x under
``--smoke``) with per-WAVE-scale deadlines, once through the no-shedding
baseline and once through ``shed="predicted-miss"`` admission control
(plus pressure degradation).  Gates: no replay ever drops a result
(delivered + shed == submitted), the shedding policy's ADMITTED deadline
hit-rate stays >= ``--overload-hit-floor`` at loads >= 3x, and (full
runs) the baseline's overall hit-rate collapses below
``--overload-baseline-max`` at 10x -- overload is real, admission control
is what survives it.  Smoke writes ``BENCH_serving.overload.smoke.json``;
full runs merge ``overload_rows`` into ``BENCH_serving.json``.

``--minibatch`` is the giant-graph ladder (DESIGN.md section 16): a
power-law host graph (10^5 vertices on full runs) with its features
pinned once in a ``FeatureStore``, a skewed seed-vertex query stream
answered by ``MiniBatchServeEngine`` (neighbor sampling ->
cache-or-wave -> per-wave store gather) vs the naive per-query
sample+run loop.  Gates: bitwise parity against the per-seed oracle
BEFORE any merge, cache hit-rate >= ``--minibatch-hit-floor`` under the
skewed stream, and seed throughput >= ``--minibatch-tol`` x naive
(smoke artifact ``BENCH_serving.minibatch.smoke.json``, full runs merge
``minibatch_rows`` into ``BENCH_serving.json``).

  PYTHONPATH=src python -m benchmarks.run --only serving
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke              # CI gate
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke --continuous # + online gate
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke --overload   # + overload gate
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_serving --mesh --smoke   # + mesh gate
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit, geomean
from repro.jax_cache import configure_compile_cache
from repro.serving.graph_engine import GraphServeEngine, random_requests
from repro.serving.scheduler import ContinuousGraphServer

_OUT = pathlib.Path(__file__).resolve().parents[1] / "BENCH_serving.json"
_SMOKE_OUT = _OUT.with_name("BENCH_serving.smoke.json")
_CONT_SMOKE_OUT = _OUT.with_name("BENCH_serving.continuous.smoke.json")
_MESH_SMOKE_OUT = _OUT.with_name("BENCH_serving.multidevice.smoke.json")
_SUBMESH_SMOKE_OUT = _OUT.with_name("BENCH_serving.submesh.smoke.json")
_OVERLOAD_SMOKE_OUT = _OUT.with_name("BENCH_serving.overload.smoke.json")
_MINIBATCH_SMOKE_OUT = _OUT.with_name("BENCH_serving.minibatch.smoke.json")

F_IN = 64
SIZES = (56, 100, 150)            # -> buckets 64, 128, 256


def _measure_naive(eng: GraphServeEngine, reqs, rounds: int):
    """Best round's per-request wall clocks (list) for the naive loop."""
    best_total, best_lat = float("inf"), None
    for _ in range(rounds):
        lat = []
        for r in reqs:
            t0 = time.perf_counter()
            eng.run_naive([r])
            lat.append(time.perf_counter() - t0)
        if sum(lat) < best_total:
            best_total, best_lat = sum(lat), lat
    return best_lat, best_total


def _measure_served(eng: GraphServeEngine, reqs, rounds: int):
    """Best round's per-request latencies, total, wave count, and per-wave
    (real, slots) occupancy.

    A request's latency is its admission wave's dispatch wall clock (all
    requests of a wave share it) scaled by the round's host-prep overhead
    -- the full ``serve()`` wall divided proportionally over the waves --
    so both the latency columns and the throughput comparison against the
    naive loop (whose per-request timing also includes ITS host prep:
    normalization, padding, tensor construction) are apples to apples."""
    best = (float("inf"), None, 0, [])
    for _ in range(rounds):
        w0 = len(eng.wave_walls)
        l0 = len(eng.wave_loads)
        t0 = time.perf_counter()
        res = eng.serve(reqs)
        total = time.perf_counter() - t0
        walls = eng.wave_walls[w0:]
        loads = eng.wave_loads[l0:]
        prep_scale = total / sum(walls)
        wave_of = {r.request_id: r.wave for r in res}
        first_wave = min(wave_of.values())
        lat = [walls[wave_of[r.request_id] - first_wave] * prep_scale
               for r in reqs]
        if total < best[0]:
            best = (total, lat, len(walls), loads)
    return best[1], best[0], best[2], best[3]


def _padding_efficiency(loads) -> float:
    """Aggregate real/slots over a wave-load series (1.0 = no padding)."""
    slots = sum(s for _, s in loads)
    return (sum(r for r, _ in loads) / slots) if slots else 1.0


def _bench_model(model: str, n_requests: int, slots: int, rounds: int
                 ) -> dict:
    reqs = random_requests(n_requests, f_in=F_IN, sizes=SIZES, seed=7)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=slots, weight_seed=0)
    # warm both paths (compile + trace) before timing
    eng.serve(reqs)
    eng.run_naive(reqs)
    naive_lat, served_lat = [None], [None]
    naive_total, served_total = [float("inf")], [float("inf")]
    waves_per_round, wave_loads = 0, []
    for _ in range(rounds):                      # interleave per round
        lat, tot, waves_per_round, loads = _measure_served(eng, reqs, 1)
        if tot < served_total[0]:
            served_total[0], served_lat[0], wave_loads = tot, lat, loads
        lat, tot = _measure_naive(eng, reqs, 1)
        if tot < naive_total[0]:
            naive_total[0], naive_lat[0] = tot, lat
    row = {
        "model": model, "n_requests": n_requests, "slots": slots,
        "buckets": eng.buckets, "waves_per_round": waves_per_round,
        # per-wave (real, slots) occupancy + aggregate real/slots: how much
        # of every dispatched wave carried real requests (InferenceReport
        # wave_real/wave_slots, recorded by the engine per dispatch)
        "wave_loads": [[r, s] for r, s in wave_loads],
        "padding_efficiency": _padding_efficiency(wave_loads),
        "naive_p50_ms": float(np.percentile(naive_lat[0], 50) * 1e3),
        "naive_p99_ms": float(np.percentile(naive_lat[0], 99) * 1e3),
        "naive_throughput_rps": n_requests / naive_total[0],
        "served_p50_ms": float(np.percentile(served_lat[0], 50) * 1e3),
        "served_p99_ms": float(np.percentile(served_lat[0], 99) * 1e3),
        "served_throughput_rps": n_requests / served_total[0],
    }
    row["throughput_speedup"] = (row["served_throughput_rps"]
                                 / row["naive_throughput_rps"])
    emit(f"serving.{model}", row["served_p50_ms"] * 1e3,
         f"naive_p50={row['naive_p50_ms']:.2f}ms "
         f"served_p50={row['served_p50_ms']:.2f}ms "
         f"throughput={row['served_throughput_rps']:.1f}rps "
         f"({row['throughput_speedup']:.2f}x naive) "
         f"pad_eff={row['padding_efficiency']:.2f}")
    return row


def _replay_continuous(eng: GraphServeEngine, reqs, arrivals, budget: float,
                       n_lanes=None, resize=False):
    """Open-loop arrival replay: submit each request when the wall clock
    passes its Poisson arrival time (deadline = arrival + ``budget``),
    polling the scheduler in between; drain flushes the tail once the
    stream ends.  Returns (results, per-request sojourn latencies,
    hit-rate, busy-span seconds, per-wave loads).  ``n_lanes`` overrides
    the scheduler's lane count (None = one per engine mesh device);
    ``resize`` switches the lanes to disjoint per-wave device groups
    (DESIGN.md section 14)."""
    srv = ContinuousGraphServer(eng, n_lanes=n_lanes, resize=resize)
    w0 = len(eng.wave_loads)
    t0 = time.monotonic()
    abs_arrival = t0 + np.asarray(arrivals)
    n, i, done = len(reqs), 0, []
    while i < n:
        now = time.monotonic()
        while i < n and abs_arrival[i] <= now:
            srv.submit(reqs[i], deadline=float(abs_arrival[i]) + budget)
            i += 1
        got = srv.poll()                     # full/deadline/age cuts stream
        done += got
        if not got:
            # nothing cuttable yet: a short bounded sleep instead of a
            # busy spin (which would compete with the dispatches we time)
            time.sleep(min(max(abs_arrival[i] - time.monotonic(), 0.0),
                           1e-3) if not srv.pending else 5e-4)
    done += srv.drain()                      # end of stream: flush the tail
    by_arrival = {r.request_id: a for r, a in zip(reqs, abs_arrival)}
    lat = [r.completed_at - by_arrival[r.request_id] for r in done]
    hits = [bool(r.deadline_met) for r in done]
    span = max(r.completed_at for r in done) - t0      # from stream start
    return done, lat, float(np.mean(hits)), float(span), eng.wave_loads[w0:]


def _best_replay(eng: GraphServeEngine, reqs, rate: float, budget: float,
                 rounds: int, n_lanes=None, resize=False):
    """Best-of-rounds Poisson replay, the ONE arrival methodology every
    continuous ladder shares: per round, seeded inter-arrival draws
    (seed 100+r), a full `_replay_continuous`, and an all-served
    assertion; the round with the smallest busy span wins.  Returns
    (span, hit_rate, latencies, wave_loads, last_arrival).  Ladders that
    COMPARE lane configs on one engine use `_interleaved_replays`, which
    runs the same rounds round-robin across configs."""
    best = None
    for r in range(rounds):
        rng = np.random.default_rng(100 + r)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, len(reqs)))
        results, lat, hit_rate, span, loads = _replay_continuous(
            eng, reqs, arrivals, budget, n_lanes=n_lanes, resize=resize)
        assert len(results) == len(reqs)
        if best is None or span < best[0]:
            best = (span, hit_rate, lat, loads, float(arrivals[-1]))
    return best


def _interleaved_replays(eng: GraphServeEngine, reqs, rate: float,
                         budget: float, rounds: int, configs) -> dict:
    """`_best_replay` for lane COMPARISONS: round r replays every config
    in ``configs`` (tuples of (key, n_lanes, resize)) once, on the same
    seeded arrivals, before round r+1 starts.  Sequential best-of-rounds
    per config would let slow machine drift mid-bench land entirely on
    whichever config runs last (observed: a whole ladder's multi-lane
    configs measuring 0.8-0.9x because they always follow single-lane);
    round-robin spreads the drift across all configs, so the per-config
    best spans stay comparable.  Returns {key: _best_replay tuple}."""
    best = {}
    for r in range(rounds):
        rng = np.random.default_rng(100 + r)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, len(reqs)))
        for key, n_lanes, resize in configs:
            results, lat, hit_rate, span, loads = _replay_continuous(
                eng, reqs, arrivals, budget, n_lanes=n_lanes, resize=resize)
            assert len(results) == len(reqs)
            if key not in best or span < best[key][0]:
                best[key] = (span, hit_rate, lat, loads,
                             float(arrivals[-1]))
    return best


def _bench_continuous(model: str, n_requests: int, slots: int, rounds: int,
                      load: float, budget_factor: float) -> dict:
    """Continuous-vs-sync ladder for one model, same request SET and same
    arrival PROCESS for both paths.

    The engine is warmed (compile + trace + wall samples) by a sync serve;
    ``serve_wall`` (best-of-rounds) is the pure batch-service time and the
    capacity estimate.  The Poisson stream arrives at ``load`` x that
    capacity; each request's deadline is ``budget_factor`` x the batch
    service span past its arrival.  The synchronous baseline serving the
    SAME stream must gather the whole batch before ``serve`` can admit it
    (PR-3's engine is batch-synchronous by construction), so its stream
    span is ``last_arrival + serve_wall``; the continuous scheduler
    overlaps arrival with service, which is exactly the win this row
    measures.  ``sync_service_throughput_rps`` keeps the arrival-free
    batch number for reference."""
    reqs = random_requests(n_requests, f_in=F_IN, sizes=SIZES, seed=7)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=slots, weight_seed=0)
    eng.serve(reqs)                          # warm: compile + trace + walls
    serve_wall = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        eng.serve(reqs)
        serve_wall = min(serve_wall, time.perf_counter() - t0)
    capacity = n_requests / serve_wall       # measured, incl. fragmentation
    rate = load * capacity
    budget = budget_factor * serve_wall
    span, hit_rate, lat, loads, last_arrival = _best_replay(
        eng, reqs, rate, budget, rounds)
    sync_span = last_arrival + serve_wall              # gather, then serve
    row = {
        "mode": "continuous", "model": model, "n_requests": n_requests,
        "slots": slots, "load": load, "budget_factor": budget_factor,
        "deadline_budget_ms": budget * 1e3,
        "arrival_rate_rps": rate,
        "deadline_hit_rate": hit_rate,
        "wave_loads": [[r_, s] for r_, s in loads],
        "padding_efficiency": _padding_efficiency(loads),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "throughput_rps": n_requests / span,
        "sync_stream_throughput_rps": n_requests / sync_span,
        "sync_service_throughput_rps": capacity,
    }
    row["throughput_vs_sync"] = (row["throughput_rps"]
                                 / row["sync_stream_throughput_rps"])
    emit(f"serving.continuous.{model}", row["p50_ms"] * 1e3,
         f"hit_rate={hit_rate:.2f} p99={row['p99_ms']:.2f}ms "
         f"throughput={row['throughput_rps']:.1f}rps "
         f"({row['throughput_vs_sync']:.2f}x sync gather+serve) "
         f"pad_eff={row['padding_efficiency']:.2f}")
    return row


def _continuous_parity(model: str) -> None:
    """Continuous-vs-naive bitwise parity on a fresh engine, under an
    actual arrival replay (the --smoke --continuous correctness half)."""
    reqs = random_requests(6, f_in=F_IN, sizes=SIZES[:2], seed=13)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7, slots=3)
    srv = ContinuousGraphServer(eng, max_wait=0.01)
    done = []
    for r in reqs:
        srv.submit(r, deadline=time.monotonic() + 60.0)
        done += srv.poll()
    while srv.pending:
        done += srv.drain()
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    for got in done:
        if not np.array_equal(got.logits, naive[got.request_id].logits):
            sys.exit(f"continuous parity FAILED: {model} request "
                     f"{got.request_id} differs from per-request engine")
    if eng.executor.trace_count > len(eng.buckets):
        sys.exit(f"continuous trace regression: {eng.executor.trace_count} "
                 f"traces for {len(eng.buckets)} buckets")
    emit(f"serving.continuous.parity.{model}", 0.0,
         f"{len(reqs)} requests bitwise OK, "
         f"{eng.executor.trace_count} traces / {len(eng.buckets)} buckets")


def _bench_multidevice(model: str, n_requests: int, rounds: int,
                       load: float, budget_factor: float) -> dict:
    """Single-lane vs multi-lane continuous serving on the cores mesh.

    One device-sharded engine (waves split over every visible device,
    requests LPT-binned by perf_model cost); the SAME Poisson stream is
    replayed through a single-lane scheduler and a one-lane-per-device
    scheduler.  Gates (``--mesh --smoke``): sharded-vs-naive bitwise
    parity, <= one trace per (bucket, lane count), and multi-lane
    throughput >= ``--lane-tol`` x single-lane (DESIGN.md section 12).
    """
    from repro.distributed import sharding as dist_sharding
    mesh = dist_sharding.cores_mesh()
    devices = int(mesh.devices.size)
    slots = devices * max(1, 4 // devices)     # >= 4, divisible by devices
    reqs = random_requests(n_requests, f_in=F_IN, sizes=SIZES, seed=7)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=slots, weight_seed=0, mesh=mesh)
    served = eng.serve(reqs)                 # warm: compile + trace + walls
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    for r in served:
        if not np.array_equal(r.logits, naive[r.request_id].logits):
            sys.exit(f"sharded parity FAILED: {model} request "
                     f"{r.request_id} differs from per-request engine "
                     f"on the {devices}-device mesh")
    if eng.executor.trace_count > len(eng.buckets):
        sys.exit(f"sharded trace regression: {eng.executor.trace_count} "
                 f"traces for {len(eng.buckets)} buckets")
    serve_wall = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        eng.serve(reqs)
        serve_wall = min(serve_wall, time.perf_counter() - t0)
    capacity = n_requests / serve_wall
    rate = load * capacity
    budget = budget_factor * serve_wall
    lane_configs = [(1, 1, False)]
    if devices > 1:                          # single device: both identical
        lane_configs.append((devices, devices, False))
    best = _interleaved_replays(eng, reqs, rate, budget, rounds,
                                lane_configs)
    lanes_stats = {}
    for n_lanes, _, _ in lane_configs:
        span, hit_rate, lat, loads, _ = best[n_lanes]
        lanes_stats[n_lanes] = {
            "throughput_rps": n_requests / span,
            "deadline_hit_rate": hit_rate,
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "padding_efficiency": _padding_efficiency(loads),
        }
    multi = lanes_stats[devices]
    single = lanes_stats[1]
    row = {
        "mode": "multidevice", "model": model, "n_requests": n_requests,
        "devices": devices, "slots": slots, "load": load,
        "budget_factor": budget_factor,
        "sync_sharded_throughput_rps": capacity,
        "single_lane": single, "multi_lane": multi,
        "lane_speedup": (multi["throughput_rps"]
                         / single["throughput_rps"]),
    }
    emit(f"serving.multidevice.{model}", multi["p99_ms"] * 1e3,
         f"devices={devices} slots={slots} "
         f"multi_lane={multi['throughput_rps']:.1f}rps "
         f"({row['lane_speedup']:.2f}x single-lane) "
         f"hit_rate={multi['deadline_hit_rate']:.2f} "
         f"pad_eff={multi['padding_efficiency']:.2f}")
    return row


def run_mesh(*, smoke: bool = False, fast: bool = True, load: float = 2.0,
             budget_factor: float = 2.0, lane_tol: float = 1.0,
             write_json: bool = True) -> list:
    """Multidevice ladder (``--mesh``): parity + trace gates, then the
    single-lane vs multi-lane continuous comparison per model.  Smoke
    writes ``BENCH_serving.multidevice.smoke.json`` (the multidevice CI
    job's artifact); a full run merges ``multidevice_rows`` into
    ``BENCH_serving.json`` without disturbing the sync/continuous rows."""
    models, n_requests, rounds = _scale(smoke, fast)
    # the lane comparison needs enough arrivals to fill waves past the
    # 8-slot mesh AND a long enough busy span that scheduler-noise doesn't
    # swamp the single-vs-multi-lane delta: 16 requests keep the CI smoke
    # job short; full runs stretch to 32 and take extra best-of rounds
    # (replays are cheap next to the warmup compiles, and on an emulated
    # mesh -- 8 devices timesharing few cores -- per-round noise is large)
    n_requests = 16 if smoke else 32
    rounds = rounds if smoke else max(rounds, 5)
    rows = [_bench_multidevice(m, n_requests, rounds, load, budget_factor)
            for m in models]
    payload = {
        "bench": "multi-lane device-sharded continuous serving",
        "device": jax.default_backend(),
        "devices": jax.device_count(),
        "rounds": rounds,
        "rows": rows,
    }
    if smoke:
        # the smoke artifact is a CI diagnostic: write it even when the
        # gate below fails, so the uploaded json shows WHICH row lagged
        _MESH_SMOKE_OUT.write_text(json.dumps(payload, indent=2) + "\n")
    lagging = [r for r in rows if r["lane_speedup"] < lane_tol]
    if lagging:
        # gate BEFORE the merge: a failed run must not overwrite the
        # recorded trajectory in BENCH_serving.json
        sys.exit(f"multi-lane throughput below {lane_tol}x single-lane: "
                 f"{[(r['model'], round(r['lane_speedup'], 2)) for r in lagging]}")
    if not smoke and write_json:
        data = json.loads(_OUT.read_text()) if _OUT.exists() else {}
        data["multidevice_rows"] = rows
        data["multidevice_devices"] = payload["devices"]
        _OUT.write_text(json.dumps(data, indent=2) + "\n")
    return rows


def _warm_submeshes(eng: GraphServeEngine, mesh, devices: int) -> set:
    """Compile the submesh programs the resize policy can reach, so the
    timed replays measure dispatch, not jit.

    XLA compiles one executable per device PLACEMENT -- the abstract-mesh
    trace is shared across equal-size groups, the binary is not -- so each
    group size is warmed at EVERY aligned offset (its uniform partition),
    not just at device 0; a replay whose plan lands a group on unwarmed
    devices would eat a full compile mid-stream.  Each group dispatches
    TWICE per bucket: the second wall is steady-state, so the engine's
    recorded ``group_walls`` (the resize scheduler's per-size EWMA seeds,
    taken as the min) are not poisoned by the ~1000x compile outlier.
    Returns the warmed group sizes."""
    from repro.distributed import sharding as dist_sharding
    from repro.serving.graph_engine import GraphRequest
    sizes, s = set(), 1
    while s <= devices:
        if eng.slots % s == 0:
            sizes.add(s)
        s *= 2
    dummy = GraphRequest(np.eye(2, dtype=np.float32),
                         np.zeros((2, eng.f_in), np.float32), request_id=-1)
    for size in sorted(sizes):
        n_groups = devices // size
        part = [size] * n_groups + [1] * (devices - size * n_groups)
        for sub in dist_sharding.partition_mesh(mesh, part)[:n_groups]:
            for bucket in eng.buckets:
                for _ in range(2):
                    eng.finish_wave(eng.begin_wave(bucket, [dummy],
                                                   submesh=sub))
    return sizes


def _bench_submesh(model: str, n_requests: int, rounds: int,
                   load: float, budget_factor: float) -> dict:
    """Disjoint-group resize dispatch vs the shared-mesh lanes it replaces.

    One device-sharded engine; the SAME Poisson stream is replayed through
    (a) a single-lane scheduler, (b) the PR-5 shared-mesh one-lane-per-
    device scheduler, and (c) the resize scheduler dispatching every wave
    on its own disjoint device group (``plan_groups`` +
    ``begin_wave(submesh=...)``).  Gates (``--mesh --submesh --smoke``):
    resize-vs-naive bitwise parity, <= one trace per (bucket, group size),
    and submesh multi-lane throughput >= ``--lane-tol`` x single-lane.
    The row also records the shared-mesh lane speedup so the acceptance
    comparison (submesh >= shared baseline) reads from one artifact.
    """
    from repro.distributed import sharding as dist_sharding
    mesh = dist_sharding.cores_mesh()
    devices = int(mesh.devices.size)
    slots = devices * max(1, 4 // devices)     # >= 4, divisible by devices
    reqs = random_requests(n_requests, f_in=F_IN, sizes=SIZES, seed=7)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=slots, weight_seed=0, mesh=mesh)
    eng.serve(reqs)                          # warm the full-mesh program
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    sizes = _warm_submeshes(eng, mesh, devices)
    traces0 = eng.executor.trace_count
    # parity gate: one resize replay, every result bitwise == run_naive
    rng = np.random.default_rng(100)
    arrivals = np.cumsum(rng.exponential(0.002, len(reqs)))
    done, _, _, _, _ = _replay_continuous(eng, reqs, arrivals, 60.0,
                                          resize=True)
    for r in done:
        if not np.array_equal(r.logits, naive[r.request_id].logits):
            sys.exit(f"submesh parity FAILED: {model} request "
                     f"{r.request_id} differs from per-request engine "
                     f"under disjoint-group dispatch")
    if eng.executor.trace_count != traces0:
        sys.exit(f"submesh trace regression: {model} grew "
                 f"{eng.executor.trace_count - traces0} traces past the "
                 f"{len(eng.buckets)} buckets x {len(sizes)} group sizes "
                 f"warmup")
    serve_wall = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        eng.serve(reqs)
        serve_wall = min(serve_wall, time.perf_counter() - t0)
    capacity = n_requests / serve_wall
    rate = load * capacity
    budget = budget_factor * serve_wall
    configs = (("single_lane", 1, False),
               ("shared_multi_lane", devices, False),
               ("submesh_multi_lane", devices, True))
    best = _interleaved_replays(eng, reqs, rate, budget, rounds, configs)
    stats = {}
    for key, _, _ in configs:
        span, hit_rate, lat, loads, _ = best[key]
        stats[key] = {
            "throughput_rps": n_requests / span,
            "deadline_hit_rate": hit_rate,
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "padding_efficiency": _padding_efficiency(loads),
        }
    single = stats["single_lane"]["throughput_rps"]
    row = {
        "mode": "submesh", "model": model, "n_requests": n_requests,
        "devices": devices, "slots": slots, "load": load,
        "budget_factor": budget_factor,
        "group_sizes_warmed": sorted(sizes),
        "sync_sharded_throughput_rps": capacity,
        **stats,
        "lane_speedup": (stats["submesh_multi_lane"]["throughput_rps"]
                         / single),
        "shared_lane_speedup": (stats["shared_multi_lane"]["throughput_rps"]
                                / single),
    }
    row["submesh_vs_shared"] = (row["lane_speedup"]
                                / row["shared_lane_speedup"])
    emit(f"serving.submesh.{model}",
         stats["submesh_multi_lane"]["p99_ms"] * 1e3,
         f"devices={devices} "
         f"submesh={stats['submesh_multi_lane']['throughput_rps']:.1f}rps "
         f"({row['lane_speedup']:.2f}x single-lane, shared-mesh lanes "
         f"{row['shared_lane_speedup']:.2f}x) "
         f"hit_rate={stats['submesh_multi_lane']['deadline_hit_rate']:.2f}")
    return row


def run_submesh(*, smoke: bool = False, fast: bool = True, load: float = 2.0,
                budget_factor: float = 2.0, lane_tol: float = 1.0,
                write_json: bool = True) -> list:
    """Disjoint-submesh ladder (``--mesh --submesh``): resize parity +
    per-(bucket, group size) trace gates, then single-lane vs shared-mesh
    lanes vs disjoint-group lanes on the same Poisson stream.  Smoke
    writes ``BENCH_serving.submesh.smoke.json`` (the multidevice CI job's
    artifact); a full run merges ``submesh_rows`` into
    ``BENCH_serving.json`` without disturbing the other ladders."""
    models, n_requests, rounds = _scale(smoke, fast)
    n_requests = 16 if smoke else 32           # match the --mesh ladder
    rounds = rounds if smoke else max(rounds, 5)
    rows = [_bench_submesh(m, n_requests, rounds, load, budget_factor)
            for m in models]
    payload = {
        "bench": "disjoint-submesh resize dispatch vs shared-mesh lanes",
        "device": jax.default_backend(),
        "devices": jax.device_count(),
        "rounds": rounds,
        "rows": rows,
    }
    if smoke:
        # CI diagnostic: written even on gate failure (see run_mesh)
        _SUBMESH_SMOKE_OUT.write_text(json.dumps(payload, indent=2) + "\n")
    lagging = [r for r in rows if r["lane_speedup"] < lane_tol]
    if lagging:
        # gate BEFORE the merge, so a lagging run can't pollute the rows
        sys.exit(f"submesh multi-lane throughput below {lane_tol}x "
                 f"single-lane: "
                 f"{[(r['model'], round(r['lane_speedup'], 2)) for r in lagging]}")
    if not smoke and write_json:
        data = json.loads(_OUT.read_text()) if _OUT.exists() else {}
        data["submesh_rows"] = rows
        data["submesh_devices"] = payload["devices"]
        _OUT.write_text(json.dumps(data, indent=2) + "\n")
    return rows


def _replay_overload(eng: GraphServeEngine, reqs, arrivals, budget: float,
                     shed: str, pressure_threshold: float = float("inf")):
    """Arrival replay under an overload-control policy (DESIGN.md §15).

    Like ``_replay_continuous``, but the scheduler runs with admission
    shedding: a ticket whose ``admitted`` is False will never produce a
    result, so completion means delivered + shed == submitted (asserted
    -- the zero-results-dropped gate).  Requests alternate classes (every
    4th is ``priority=1, tenant="gold"``) so the per-class counters and
    wave compositions in the recorded rows carry real data.  Returns a
    stats dict for one (policy, load) cell.
    """
    srv = ContinuousGraphServer(eng, shed=shed,
                                pressure_threshold=pressure_threshold)
    # steady-state warmup: a long-running server has dispatch history, so
    # replay a couple of deadline-less waves before starting the clock.
    # This warms the SERVER-level calibrations the admission model leans
    # on -- wall-clock per wave (host prep included), occupancy, cost
    # scale -- which no amount of engine warming can provide; a stone-cold
    # server facing a 10x burst has no feedback yet and over-admits by
    # construction (cold-start admission is pinned by the unit tests, not
    # measured here).  Results are drained and discarded.
    for r in random_requests(2 * eng.slots, f_in=F_IN, sizes=SIZES, seed=13):
        srv.submit(r, tenant="warmup")
    srv.drain()
    srv.peak_pressure = 0.0                  # gauge the replay, not warmup
    t0 = time.monotonic()
    abs_arrival = t0 + np.asarray(arrivals)
    n, i, done = len(reqs), 0, []
    tickets = []
    while i < n:
        now = time.monotonic()
        while i < n and abs_arrival[i] <= now:
            gold = i % 4 == 0
            tickets.append(srv.submit(
                reqs[i], deadline=float(abs_arrival[i]) + budget,
                priority=1 if gold else 0,
                tenant="gold" if gold else "std"))
            i += 1
        got = srv.poll()
        done += got
        if not got:
            time.sleep(min(max(abs_arrival[i] - time.monotonic(), 0.0),
                           1e-3) if not srv.pending else 5e-4)
    done += srv.drain()
    # zero-results-dropped: every submitted request either produced exactly
    # one result or is accounted in the shed log -- never silently lost
    delivered_ids = sorted(r.request_id for r in done)
    assert len(delivered_ids) == len(set(delivered_ids)), "duplicate results"
    # ticket seq -> request via the submit-order zip (warmup submissions
    # offset the raw seq, so it is NOT an index into ``reqs``)
    req_of = {int(t): r for t, r in zip(tickets, reqs)}
    shed_ids = sorted(req_of[int(t)].request_id for t in srv.shed_log)
    assert sorted(delivered_ids + shed_ids) == sorted(
        r.request_id for r in reqs), (
        f"results dropped: {len(done)} delivered + {len(shed_ids)} shed "
        f"!= {n} submitted")
    by_arrival = {r.request_id: a for r, a in zip(reqs, abs_arrival)}
    lat = [r.completed_at - by_arrival[r.request_id] for r in done]
    met = sum(bool(r.deadline_met) for r in done)
    span = (max(r.completed_at for r in done) - t0) if done else 0.0
    return {
        "submitted": n,
        "delivered": len(done),
        "shed": len(shed_ids),
        "shed_at_submit": srv.shed_at_submit,
        "shed_under_pressure": srv.shed_under_pressure,
        "met": met,
        "missed": len(done) - met,
        # overall: met deadlines over EVERYTHING submitted (a shed request
        # is a miss from the client's view); admitted: over deliveries only
        "overall_hit_rate": met / n,
        "admitted_hit_rate": (met / len(done)) if done else 1.0,
        "goodput_rps": (met / span) if span else 0.0,
        "p99_sojourn_ms": (float(np.percentile(lat, 99) * 1e3)
                           if lat else 0.0),
        "peak_pressure_s": srv.peak_pressure,
        "at_risk_admitted": sum(t.verdict == "admit-at-risk"
                                for t in tickets),
        "predicted_miss_rate": float(np.mean(
            [t.predicted_miss for t in tickets])),
        "class_stats": {
            f"{tenant}/p{prio}": {
                "admitted": s.admitted, "shed": s.shed,
                "met": s.met, "missed": s.missed}
            for (tenant, prio), s in sorted(srv.class_stats.items())},
    }


def _bench_overload(model: str, n_requests: int, loads, budget_factor: float
                    ) -> list:
    """Overload ladder for one model: Poisson replays at each load in
    ``loads`` x the measured capacity, once WITHOUT shedding
    (``shed="never"``: the pre-overload scheduler, every request admitted
    and chased) and once WITH cost-model admission control
    (``shed="predicted-miss"`` + pressure degradation at the deadline
    budget).  The deadline budget is per-WAVE scale
    (``budget_factor`` x the measured wave wall), not per-batch: at 1x
    load either policy hits nearly everything, while past saturation the
    no-shedding baseline's queue -- and so its sojourn -- grows without
    bound and its hit-rate collapses; admission control sheds the
    predicted losers at the door and keeps the ADMITTED hit-rate high.
    That asymmetry is the acceptance gate (DESIGN.md §15).
    """
    reqs = random_requests(n_requests, f_in=F_IN, sizes=SIZES, seed=7)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=4, weight_seed=0)
    eng.serve(reqs)                          # warm: compile + trace + walls
    t0 = time.perf_counter()
    eng.serve(reqs)
    serve_wall = time.perf_counter() - t0
    capacity = n_requests / serve_wall       # requests/s through full waves
    wave_wall = serve_wall * eng.slots / n_requests
    budget = budget_factor * wave_wall
    rows = []
    for load in loads:
        rate = load * capacity
        cell = {"mode": "overload", "model": model,
                "n_requests": n_requests, "slots": eng.slots,
                "load": load, "budget_ms": budget * 1e3,
                "capacity_rps": capacity, "arrival_rate_rps": rate,
                "policies": {}}
        for shed in ("never", "predicted-miss"):
            rng = np.random.default_rng(100)
            arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
            # degradation arms at HALF the budget: by the time the backlog
            # bound reaches the full deadline budget every queued request
            # is already doomed -- pruning has to start while shedding can
            # still rescue the survivors' slack
            cell["policies"][shed] = _replay_overload(
                eng, reqs, arrivals, budget, shed,
                pressure_threshold=(budget / 2 if shed == "predicted-miss"
                                    else float("inf")))
        base = cell["policies"]["never"]
        ctrl = cell["policies"]["predicted-miss"]
        emit(f"serving.overload.{model}.x{load:g}",
             ctrl["p99_sojourn_ms"] * 1e3,
             f"baseline_hit={base['overall_hit_rate']:.2f} "
             f"admitted_hit={ctrl['admitted_hit_rate']:.2f} "
             f"shed={ctrl['shed']}/{n_requests} "
             f"goodput={ctrl['goodput_rps']:.1f}rps "
             f"(baseline {base['goodput_rps']:.1f}rps)")
        rows.append(cell)
    return rows


def run_overload(*, smoke: bool = False, fast: bool = True,
                 budget_factor: float = 6.0, hit_floor: float = 0.9,
                 baseline_max: float = 0.5,
                 write_json: bool = True) -> list:
    """Overload-control ladder (``--overload``): admission shedding vs the
    no-shedding baseline at 1x/3x/10x the measured capacity.

    Gates: zero results dropped in every replay (asserted inside
    ``_replay_overload``); at every load >= 3x the shedding policy's
    ADMITTED deadline hit-rate >= ``hit_floor``; and at the 10x point the
    no-shedding baseline's overall hit-rate < ``baseline_max`` -- i.e.
    the replay genuinely overloads the engine and admission control is
    what keeps served requests on deadline.  Smoke (the serving CI job)
    runs gcn only at 1x/3x and skips the baseline-collapse gate (shared
    runners make the 10x point slow and noisy); full runs merge
    ``overload_rows`` into ``BENCH_serving.json``."""
    models, _, _ = _scale(smoke, fast)
    loads = (1, 3) if smoke else (1, 3, 10)
    # full runs use a DEEP replay (24 waves' worth): at 10x the whole
    # backlog lands inside ~2.4 wave walls, so time-to-clear (~24 walls)
    # dwarfs the 6-wall deadline budget and the no-shedding baseline
    # collapses for real -- and the shedding policy still delivers enough
    # requests at 10x that the hit-rate gate is not one borderline miss
    # away from binomial noise
    n_requests = 16 if smoke else 96
    rows = []
    for m in models:
        rows.extend(_bench_overload(m, n_requests, loads, budget_factor))
    payload = {
        "bench": "overload-controlled serving: admission shedding vs "
                 "no-shedding baseline",
        "device": jax.default_backend(),
        "loads": list(loads),
        "hit_floor": hit_floor,
        "baseline_max": baseline_max,
        "rows": rows,
    }
    if smoke:
        # CI diagnostic: written even on gate failure (see run_mesh)
        _OVERLOAD_SMOKE_OUT.write_text(json.dumps(payload, indent=2) + "\n")
    weak = [(r["model"], r["load"],
             round(r["policies"]["predicted-miss"]["admitted_hit_rate"], 3))
            for r in rows if r["load"] >= 3
            and r["policies"]["predicted-miss"]["admitted_hit_rate"]
            < hit_floor]
    if weak:
        sys.exit(f"admitted deadline hit-rate below {hit_floor} under "
                 f"overload: {weak}")
    if not smoke:
        soft = [(r["model"], round(r["policies"]["never"]["overall_hit_rate"],
                                   3))
                for r in rows if r["load"] >= 10
                and r["policies"]["never"]["overall_hit_rate"]
                >= baseline_max]
        if soft:
            sys.exit(f"no-shedding baseline did not collapse at 10x "
                     f"(overall hit-rate >= {baseline_max}): {soft} -- "
                     f"the replay is not actually overloading the engine")
    if not smoke and write_json:
        data = json.loads(_OUT.read_text()) if _OUT.exists() else {}
        data["overload_rows"] = rows
        _OUT.write_text(json.dumps(data, indent=2) + "\n")
    return rows


def _bench_minibatch(model: str, n_vertices: int, n_queries: int, *,
                     fanouts=(8, 4), traffic_alpha: float = 1.6,
                     cache_capacity: int = 4096, chunk: int = 8) -> dict:
    """Giant-graph mini-batch serving vs the naive per-query loop
    (DESIGN.md section 16).

    ONE power-law host graph (``data.sampling.powerlaw_host_graph``) with
    its features pinned once in a ``FeatureStore``; a skewed query stream
    (seed vertices drawn under power-law weights -- hot vertices repeat,
    which is the hot-vertex cache's whole case) is answered twice:

    * **naive** -- per query, per seed: sample the subgraph, gather
      features, one ``run_naive`` dispatch.  No batching, no caching, and
      every repeat of a hot vertex pays the full sample+gather+run cost
      again;
    * **minibatch** -- ``MiniBatchServeEngine.serve_queries`` in arrival
      chunks of ``chunk`` queries: cache hits answered at the door,
      misses deduplicated across the chunk and wave-batched through the
      shape buckets, per-wave feature gather straight from the pinned
      store, results filling the LRU ``VertexCache``.

    Bitwise parity against the per-seed oracle is asserted (sys.exit)
    BEFORE any timing or artifact merge, on a throwaway front end so the
    measured cache starts cold.  The row gates (in ``run_minibatch``):
    cache hit-rate >= the floor under the skewed stream, and mini-batch
    seed throughput >= tol x naive."""
    from repro.data import graphs as graph_data
    from repro.data.sampling import powerlaw_host_graph
    from repro.serving.minibatch import FeatureStore, MiniBatchServeEngine
    rng = np.random.default_rng(3)
    graph = powerlaw_host_graph(n_vertices, avg_degree=8, seed=0)
    store = FeatureStore(rng.standard_normal((n_vertices, F_IN),
                                             dtype=np.float32))
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7,
                           slots=8, weight_seed=0)
    mb = MiniBatchServeEngine(eng, graph, store, fanouts=fanouts,
                              cache_capacity=cache_capacity)
    # skewed traffic: seed vertices drawn under power-law weights (the
    # Table VI marginal), independent of graph degree -- hot QUERY
    # vertices, not necessarily hubs
    w = graph_data.powerlaw_marginal(n_vertices, rng, alpha=traffic_alpha)
    queries = [rng.choice(n_vertices, size=int(rng.integers(1, 5)),
                          p=w).tolist() for _ in range(n_queries)]
    # parity gate FIRST, on a throwaway front end (own cold cache) so the
    # timed run below still measures a cold-start hit-rate; this also
    # warms the engine's compile + trace for both paths
    parity_mb = MiniBatchServeEngine(eng, graph, store, fanouts=fanouts,
                                     cache_capacity=cache_capacity)
    par_q = queries[:4]
    for t, want in zip(parity_mb.serve_queries(par_q),
                       parity_mb.oracle_queries(par_q)):
        if not np.array_equal(t.result(), want):
            sys.exit(f"minibatch parity FAILED: {model} query "
                     f"{t.query_id} differs from the per-seed oracle")
    emit(f"serving.minibatch.parity.{model}", 0.0,
         f"{len(par_q)} queries bitwise OK vs per-seed run_naive")
    n_seed_runs = sum(len(dict.fromkeys(q)) for q in queries)
    # naive per-query loop: every seed occurrence sampled + run one at a
    # time (repeats of hot vertices pay full price -- no cross-query state)
    from repro.serving.minibatch import SeedRequest
    t0 = time.perf_counter()
    for q in queries:
        for v in dict.fromkeys(q):
            req = SeedRequest(mb.planner.sample(v), store, request_id=-1)
            eng.run_naive([req])
    t_naive = time.perf_counter() - t0
    # mini-batch path: same traffic, arrival chunks, cold cache
    w0, waves0 = len(eng.wave_loads), eng.waves
    t0 = time.perf_counter()
    for i in range(0, len(queries), chunk):
        mb.serve_queries(queries[i:i + chunk])
    t_mb = time.perf_counter() - t0
    stats = mb.cache.stats
    row = {
        "mode": "minibatch", "model": model,
        "n_vertices": graph.n_vertices, "n_edges": graph.n_edges,
        "store_mb": store.nbytes / 2**20,
        "n_queries": n_queries, "n_seed_runs": n_seed_runs,
        "fanouts": list(fanouts), "chunk": chunk,
        "cache_capacity": cache_capacity,
        "traffic_alpha": traffic_alpha,
        "cache": stats.as_dict(),
        "hit_rate": stats.hit_rate,
        "waves": eng.waves - waves0,
        "padding_efficiency": _padding_efficiency(eng.wave_loads[w0:]),
        "naive_throughput_sps": n_seed_runs / t_naive,
        "minibatch_throughput_sps": n_seed_runs / t_mb,
    }
    row["throughput_speedup"] = (row["minibatch_throughput_sps"]
                                 / row["naive_throughput_sps"])
    emit(f"serving.minibatch.{model}", t_mb / n_queries * 1e6,
         f"graph={graph.n_vertices}v/{graph.n_edges}e "
         f"hit_rate={row['hit_rate']:.2f} "
         f"throughput={row['minibatch_throughput_sps']:.1f} seeds/s "
         f"({row['throughput_speedup']:.2f}x naive) "
         f"waves={row['waves']} pad_eff={row['padding_efficiency']:.2f}")
    return row


def run_minibatch(*, smoke: bool = False, fast: bool = True,
                  hit_floor: float = 0.5, tput_tol: float = 2.0,
                  write_json: bool = True) -> list:
    """Mini-batch serving ladder (``--minibatch``): oracle parity, then
    the cached+batched front end vs the naive per-query sample+run loop
    on one giant power-law host graph under skewed traffic.

    Gates (all BEFORE the artifact merge): bitwise parity per model
    (asserted inside ``_bench_minibatch``), cache hit-rate >=
    ``hit_floor`` under the skewed stream, and mini-batch seed
    throughput >= ``tput_tol`` x naive.  Smoke (the serving CI job) runs
    gcn on a scaled-down graph and writes
    ``BENCH_serving.minibatch.smoke.json``; full runs use a 10^5-vertex
    host graph and merge ``minibatch_rows`` into ``BENCH_serving.json``
    without disturbing the other ladders."""
    models, _, _ = _scale(smoke, fast)
    n_vertices = 20_000 if smoke else 100_000
    n_queries = 60 if smoke else 200
    rows = [_bench_minibatch(m, n_vertices, n_queries) for m in models]
    payload = {
        "bench": "giant-graph mini-batch serving: sampler + pinned store "
                 "+ hot-vertex cache vs naive per-query loop",
        "device": jax.default_backend(),
        "hit_floor": hit_floor,
        "tput_tol": tput_tol,
        "rows": rows,
    }
    if smoke:
        # CI diagnostic: written even on gate failure (see run_mesh)
        _MINIBATCH_SMOKE_OUT.write_text(json.dumps(payload, indent=2) + "\n")
    cold = [(r["model"], round(r["hit_rate"], 3)) for r in rows
            if r["hit_rate"] < hit_floor]
    if cold:
        sys.exit(f"minibatch cache hit-rate below {hit_floor} under "
                 f"skewed traffic: {cold}")
    slow = [(r["model"], round(r["throughput_speedup"], 2)) for r in rows
            if r["throughput_speedup"] < tput_tol]
    if slow:
        # gate BEFORE the merge, so a lagging run can't pollute the rows
        sys.exit(f"minibatch throughput below {tput_tol}x the naive "
                 f"per-query loop: {slow}")
    if not smoke and write_json:
        data = json.loads(_OUT.read_text()) if _OUT.exists() else {}
        data["minibatch_rows"] = rows
        _OUT.write_text(json.dumps(data, indent=2) + "\n")
    return rows


def _scale(smoke: bool, fast: bool) -> tuple:
    """(models, n_requests, rounds) for the sync AND continuous ladders --
    one source of truth so the smoke artifact's metadata can't drift from
    the measurements."""
    if smoke:
        return ("gcn",), 8, 2
    if fast:
        return ("gcn", "sage"), 16, 3
    return ("gcn", "sage", "gin", "sgc"), 16, 3


def run_continuous(fast: bool = True, *, smoke: bool = False,
                   load: float = 2.0, budget_factor: float = 2.0) -> list:
    """Continuous-mode rows (one per model); smoke = gcn only."""
    models, n_requests, rounds = _scale(smoke, fast)
    return [_bench_continuous(m, n_requests, 4, rounds, load, budget_factor)
            for m in models]


def _parity(model: str) -> None:
    """Bitwise served-vs-naive parity on a fresh engine (the smoke gate's
    correctness half; the full per-model sweep lives in tests)."""
    reqs = random_requests(6, f_in=F_IN, sizes=SIZES[:2], seed=11)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=16, n_classes=7, slots=3)
    served = eng.serve(reqs)
    naive = eng.run_naive(reqs)
    for s, n in zip(served, naive):
        if not np.array_equal(s.logits, n.logits):
            sys.exit(f"serving parity FAILED: {model} request "
                     f"{s.request_id} differs from per-request engine")
    emit(f"serving.parity.{model}", 0.0, f"{len(reqs)} requests bitwise OK")


def run(fast: bool = True, *, smoke: bool = False,
        write_json: bool = True, continuous: bool = True,
        load: float = 2.0, budget_factor: float = 2.0) -> list:
    models, n_requests, rounds = _scale(smoke, fast)
    slots = 4
    rows = [_bench_model(m, n_requests, slots, rounds) for m in models]
    gm = geomean(r["throughput_speedup"] for r in rows)
    payload = {
        "bench": "batched graph serving vs naive per-request loop",
        "device": jax.default_backend(),
        "rounds": rounds,
        "rows": rows,
        "geomean_throughput_speedup": gm,
    }
    if continuous:
        payload["continuous_rows"] = run_continuous(
            fast, smoke=smoke, load=load, budget_factor=budget_factor)
    if write_json:
        _OUT.write_text(json.dumps(payload, indent=2) + "\n")
    if smoke:
        # one smoke invocation produces BOTH workflow artifacts: the sync
        # rows and (with --continuous) the continuous rows, separately,
        # so the CI serving job runs the bench exactly once
        sync_payload = {k: v for k, v in payload.items()
                        if k != "continuous_rows"}
        _SMOKE_OUT.write_text(json.dumps(sync_payload, indent=2) + "\n")
        if continuous:
            cont_payload = {
                "bench": "continuous deadline-aware serving vs sync "
                         "gather+serve",
                "device": payload["device"], "rounds": rounds,
                "rows": payload["continuous_rows"],
            }
            _CONT_SMOKE_OUT.write_text(
                json.dumps(cont_payload, indent=2) + "\n")
    emit("serving.geomean_throughput_speedup", 0.0,
         f"{gm:.2f}x -> {(_SMOKE_OUT if smoke else _OUT).name}")
    return rows + payload.get("continuous_rows", [])


if __name__ == "__main__":
    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: gcn only, bitwise parity check, loose "
                         "throughput gate, writes BENCH_serving.smoke.json "
                         "(workflow artifact) instead of BENCH_serving.json")
    ap.add_argument("--full", action="store_true",
                    help="all four models")
    ap.add_argument("--continuous", action="store_true",
                    help="with --smoke: gate the continuous scheduler too "
                         "(bitwise continuous-vs-naive parity, deadline "
                         "hit-rate floor, throughput vs sync serve) and "
                         "write BENCH_serving.continuous.smoke.json")
    ap.add_argument("--mesh", action="store_true",
                    help="multidevice mode: device-sharded waves over a "
                         "cores mesh of every visible device (run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=8 to emulate), gating sharded parity, trace "
                         "count, and multi-lane vs single-lane continuous "
                         "throughput; with --smoke writes "
                         "BENCH_serving.multidevice.smoke.json, otherwise "
                         "merges multidevice_rows into BENCH_serving.json")
    ap.add_argument("--submesh", action="store_true",
                    help="with --mesh: run the disjoint-submesh ladder "
                         "instead -- resize-scheduler parity, the per-"
                         "(bucket, group size) trace bound, and single-"
                         "lane vs shared-mesh vs disjoint-group "
                         "throughput; with --smoke writes "
                         "BENCH_serving.submesh.smoke.json, otherwise "
                         "merges submesh_rows into BENCH_serving.json")
    ap.add_argument("--overload", action="store_true",
                    help="overload-control ladder: Poisson replays at "
                         "1x/3x/10x the measured capacity (1x/3x with "
                         "--smoke), shed='predicted-miss' admission "
                         "control vs the no-shedding baseline, gating "
                         "zero-results-dropped + the admitted hit-rate "
                         "floor (+ the 10x baseline-collapse check on "
                         "full runs); with --smoke writes "
                         "BENCH_serving.overload.smoke.json, otherwise "
                         "merges overload_rows into BENCH_serving.json")
    ap.add_argument("--minibatch", action="store_true",
                    help="giant-graph mini-batch ladder: neighbor-sampled "
                         "queries over one power-law host graph, pinned "
                         "FeatureStore gather, hot-vertex cache -- gating "
                         "bitwise oracle parity, the cache hit-rate floor "
                         "under skewed traffic, and throughput vs the "
                         "naive per-query sample+run loop; with --smoke "
                         "writes BENCH_serving.minibatch.smoke.json, "
                         "otherwise merges minibatch_rows into "
                         "BENCH_serving.json")
    ap.add_argument("--minibatch-hit-floor", type=float, default=0.5,
                    help="minibatch gate: fail if the hot-vertex cache "
                         "hit-rate < floor under the skewed query stream")
    ap.add_argument("--minibatch-tol", type=float, default=2.0,
                    help="minibatch gate: fail if mini-batch seed "
                         "throughput < tol x the naive per-query loop.  "
                         "CI's shared runners pass a looser value")
    ap.add_argument("--overload-hit-floor", type=float, default=0.9,
                    help="overload gate: fail if the shedding policy's "
                         "ADMITTED deadline hit-rate < floor at any "
                         "load >= 3x capacity")
    ap.add_argument("--overload-baseline-max", type=float, default=0.5,
                    help="overload gate (full runs): fail unless the "
                         "no-shedding baseline's overall hit-rate < max "
                         "at 10x capacity (the replay must genuinely "
                         "overload the engine)")
    ap.add_argument("--overload-budget-factor", type=float, default=6.0,
                    help="overload deadline budget as a multiple of the "
                         "measured WAVE wall (per-wave scale, unlike "
                         "--budget-factor's per-batch scale)")
    ap.add_argument("--lane-tol", type=float, default=1.0,
                    help="mesh gate: fail if multi-lane continuous "
                         "throughput < tol x single-lane on the same "
                         "sharded engine.  CI passes a looser value "
                         "(shared-runner timing noise)")
    ap.add_argument("--tol", type=float, default=1.5,
                    help="throughput gate: fail if served throughput < tol "
                         "x naive.  Default asserts the headline batching "
                         "win on a quiet machine; CI's shared runners pass "
                         "a looser value that still catches the "
                         "batching-does-more-work regression class")
    ap.add_argument("--hit-floor", type=float, default=0.9,
                    help="continuous gate: fail if deadline hit-rate < floor "
                         "at the default load")
    ap.add_argument("--cont-tol", type=float, default=1.0,
                    help="continuous gate: fail if continuous throughput < "
                         "tol x the synchronous serve path.  CI's shared "
                         "runners pass a looser value (timing noise); the "
                         "default asserts continuous keeps up with sync on "
                         "a quiet machine")
    ap.add_argument("--load", type=float, default=2.0,
                    help="continuous offered load as a multiple of the "
                         "measured wave capacity (>1 keeps the queue busy)")
    ap.add_argument("--budget-factor", type=float, default=2.0,
                    help="deadline budget as a multiple of the expected "
                         "full-service span")
    args = ap.parse_args()
    if args.submesh and not args.mesh:
        ap.error("--submesh extends the --mesh ladder; pass both")
    if args.minibatch:
        # --minibatch is its own ladder with its own gates; like --mesh it
        # does not compose with the other modes in one invocation
        if args.mesh or args.continuous or args.overload:
            ap.error("--minibatch runs its own ladder; run --mesh/"
                     "--continuous/--overload gates in their own "
                     "invocations")
        run_minibatch(smoke=args.smoke, fast=not args.full,
                      hit_floor=args.minibatch_hit_floor,
                      tput_tol=args.minibatch_tol)
        sys.exit(0)
    if args.overload:
        # --overload is its own ladder with its own gates; like --mesh it
        # does not compose with the sync/continuous flags in one invocation
        if args.mesh or args.continuous:
            ap.error("--overload runs its own ladder; run --mesh/"
                     "--continuous gates in their own invocations")
        run_overload(smoke=args.smoke, fast=not args.full,
                     budget_factor=args.overload_budget_factor,
                     hit_floor=args.overload_hit_floor,
                     baseline_max=args.overload_baseline_max)
        sys.exit(0)
    if args.mesh:
        # --mesh is its own ladder with its own gates (--lane-tol); the
        # sync/continuous gate flags do not apply to it
        if args.continuous:
            ap.error("--mesh runs its own ladder; the continuous gates "
                     "run in the (non-mesh) --smoke --continuous job")
        if args.submesh:
            run_submesh(smoke=args.smoke, fast=not args.full,
                        load=args.load, budget_factor=args.budget_factor,
                        lane_tol=args.lane_tol)
        else:
            run_mesh(smoke=args.smoke, fast=not args.full, load=args.load,
                     budget_factor=args.budget_factor,
                     lane_tol=args.lane_tol)
        sys.exit(0)
    if args.smoke:
        _parity("gcn")
        if args.continuous:
            _continuous_parity("gcn")
    bench_rows = run(fast=not args.full, smoke=args.smoke,
                     write_json=not args.smoke,
                     continuous=args.continuous or not args.smoke,
                     load=args.load, budget_factor=args.budget_factor)
    sync_rows = [r for r in bench_rows if "throughput_speedup" in r]
    cont_rows = [r for r in bench_rows if r.get("mode") == "continuous"]
    slow = [r for r in sync_rows if r["throughput_speedup"] < args.tol]
    if slow:
        sys.exit(f"served throughput below {args.tol}x naive: "
                 f"{[(r['model'], round(r['throughput_speedup'], 2)) for r in slow]}")
    missed = [r for r in cont_rows
              if r["deadline_hit_rate"] < args.hit_floor]
    if missed:
        sys.exit(f"continuous deadline hit-rate below {args.hit_floor}: "
                 f"{[(r['model'], round(r['deadline_hit_rate'], 3)) for r in missed]}")
    lagging = [r for r in cont_rows
               if r["throughput_vs_sync"] < args.cont_tol]
    if lagging:
        sys.exit(f"continuous throughput below {args.cont_tol}x sync serve: "
                 f"{[(r['model'], round(r['throughput_vs_sync'], 2)) for r in lagging]}")
