"""Run the GNN main path once on a TPU and check it against the reference.

    python chip_smoke.py              # one chip: whole graph, then serving
    python chip_smoke.py --chips 4    # four chips: sharded and resized
                                      # serving against the unsharded engine

One process drives the chip; nothing here starts a child.  The whole-graph
phase runs gcn on Cora at full size (2708 vertices, widths 1433 -> 16 -> 7)
twice through ``FusedModelExecutor``; the serving phase answers a few
requests through ``GraphServeEngine`` behind ``ContinuousGraphServer``.
Every output is checked against ``models.gnn.reference_forward`` (plain
float32 at ``Precision.HIGHEST``); bitwise parity between engines is
printed, not gated, since fusion and matmul passes may differ per program.

Exits nonzero, without the ``ok`` line, when the first device is not a TPU,
when a phase raises, or when a result is outside tolerance.  On success the
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Output error gate, as a fraction of max |reference|.  On a TPU an f32 dot at
# default precision multiplies in bfloat16 passes, so errors sit near 1e-3;
# a forward that skips the aggregation is off by 0.3 to 1.0 of the scale.
TOL = 2e-2
DEADLINE_S = 1.0          # per-request deadline budget in the serving phase
F_IN, HIDDEN, N_CLASSES = 1433, 16, 7      # Cora: the paper's widths


def log(phase: str, **fields) -> None:
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _request_reference(spec, weights, req):
    """Float32 reference logits of one served request."""
    from repro.data import graphs as graph_data
    from repro.models import gnn

    a_gcn, a_mean = graph_data.normalize_adjacency(req.adjacency)
    tensors = {"A": a_gcn, "A_mean": a_mean,
               "H0": np.asarray(req.features, np.float32), **weights}
    return np.asarray(gnn.reference_forward(spec, tensors))


def _worst(pairs) -> float:
    """Largest reference-relative error over (out, reference) pairs."""
    from repro.models import gnn

    return max(gnn.reference_error(o, r)[1] for o, r in pairs)


def _max_diff(xs, ys) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(xs, ys))


def whole_graph(*, dataset: str = "CO", scale: float = 1.0,
                tol: float = TOL) -> bool:
    """gcn on ``dataset`` twice through the fused executor, against the
    float32 reference.  Returns whether every check held."""
    import jax

    from repro.core import runtime
    from repro.kernels import ops
    from repro.models import gnn

    bundle = gnn.build_dense("gcn", dataset, scale=scale)
    g = bundle.graph.spec
    ex = runtime.FusedModelExecutor(strategy="dynamic")
    t0 = time.perf_counter()
    out1, _ = bundle.run(ex)
    jax.block_until_ready(out1)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out, rep = bundle.run(ex)
    jax.block_until_ready(out)
    second = time.perf_counter() - t0
    spec = gnn.make_model_spec("gcn", g.f_in, g.hidden, g.n_classes)
    ref = gnn.reference_forward(spec, bundle.tensors)
    abs_err, rel_err = gnn.reference_error(out, ref)
    out = np.asarray(out)
    shape_ok = out.shape == (g.n_vertices, g.n_classes)
    finite = bool(np.isfinite(out).all())
    log("whole_graph", model="gcn", dataset=dataset, vertices=g.n_vertices,
        widths=f"{g.f_in}->{g.hidden}->{g.n_classes}",
        use_kernels=ex.use_kernels, interpret=ops.default_interpret())
    log("whole_graph", compile_s=first, wall_s=second,
        program_wall_s=rep.fused_wall_seconds, trace_count=ex.trace_count,
        histogram=rep.histogram.tolist())
    log("whole_graph", max_abs_err=abs_err, max_rel_err=rel_err, tol=tol,
        repeat_bitwise=bool(np.array_equal(np.asarray(out1), out)),
        shape=out.shape, finite=finite)
    return shape_ok and finite and ex.trace_count == 1 and rel_err <= tol


def _engine(f_in: int, **kw):
    from repro.serving.graph_engine import GraphServeEngine

    return GraphServeEngine("gcn", f_in=f_in, hidden=HIDDEN,
                            n_classes=N_CLASSES, **kw)


def serving(*, n_requests: int = 8, f_in: int = F_IN, slots: int = 4,
            sizes=(48, 96, 160), tol: float = TOL) -> bool:
    """``n_requests`` queries with deadlines through the continuous server,
    each checked against the reference and ``run_naive``."""
    from repro.serving.graph_engine import random_requests
    from repro.serving.scheduler import ContinuousGraphServer

    eng = _engine(f_in=f_in, slots=slots)
    srv = ContinuousGraphServer(eng)
    reqs = random_requests(n_requests, f_in=f_in, sizes=sizes, seed=0)
    t0 = time.perf_counter()
    srv.warmup([r.n_vertices for r in reqs])
    warm = time.perf_counter() - t0
    now = srv.clock()
    for r in reqs:
        srv.submit(r, deadline=now + DEADLINE_S)
    done = srv.poll() + srv.drain()
    by_id = {r.request_id: r for r in done}
    served = [by_id[r.request_id].logits for r in reqs]
    naive = [r.logits for r in eng.run_naive(reqs)]
    refs = [_request_reference(eng.spec, eng.weights, r) for r in reqs]
    walls = [w.wall for w in srv.dispatch_log]
    log("serving", requests=len(reqs), answered=len(done),
        buckets=eng.buckets, waves=len(walls), warmup_s=warm,
        traces=eng.executor.trace_count)
    log("serving", p50_wave_wall_s=float(np.median(walls)),
        deadline_hits=sum(bool(r.deadline_met) for r in done),
        deadline_s=DEADLINE_S)
    err_served = _worst(zip(served, refs))
    err_naive = _worst(zip(naive, refs))
    log("serving", max_rel_err=err_served, naive_max_rel_err=err_naive,
        tol=tol, bitwise_eq_naive=all(
            np.array_equal(a, b) for a, b in zip(served, naive)),
        max_diff_naive=_max_diff(served, naive))
    finite = all(np.isfinite(s).all() for s in served)
    return (len(done) == len(reqs) and finite and err_served <= tol
            and err_naive <= tol)


def _record_devices(eng) -> list:
    """Note each wave output's device ids before the engine reads it back."""
    seen: list = []
    finish = eng.finish_wave

    def finish_and_record(inflight):
        out = inflight.pending.outs[inflight.final]
        seen.append(sorted(d.id for d in out.sharding.device_set))
        return finish(inflight)

    eng.finish_wave = finish_and_record
    return seen


def sharded(*, n_devices: int = 4, n_requests: int = 8, f_in: int = F_IN,
            slots: int = 8, sizes=(48, 96, 160), tol: float = TOL) -> bool:
    """The README's mesh paths: waves sharded over a ``cores`` mesh, then
    the same requests through disjoint resized lane groups, both against
    the unsharded engine in this process and the reference."""
    from repro.distributed.sharding import cores_mesh
    from repro.serving.graph_engine import random_requests
    from repro.serving.scheduler import ContinuousGraphServer

    reqs = random_requests(n_requests, f_in=f_in, sizes=sizes, seed=0)
    base = _engine(f_in=f_in, slots=slots)
    want = [r.logits for r in base.serve(reqs)]
    refs = [_request_reference(base.spec, base.weights, r) for r in reqs]

    eng = _engine(f_in=f_in, slots=slots, mesh=cores_mesh(n_devices))
    seen = _record_devices(eng)
    got = [r.logits for r in eng.serve(reqs)]
    mesh_sets = list(seen)
    log("sharded", devices=n_devices, waves=len(mesh_sets),
        wave_device_sets=mesh_sets)
    log("sharded", max_rel_err=_worst(zip(got, refs)),
        bitwise_eq_unsharded=all(
            np.array_equal(a, b) for a, b in zip(got, want)),
        max_diff_unsharded=_max_diff(got, want))

    srv = ContinuousGraphServer(eng, resize=True)
    srv.warmup([r.n_vertices for r in reqs])
    start = len(seen)
    for r in reqs:
        srv.submit(r)
    done = {r.request_id: r.logits for r in srv.drain()}
    group_sets = seen[start:]
    resized = [done[r.request_id] for r in reqs]
    log("resized", waves=len(group_sets), wave_device_sets=group_sets,
        group_sizes=[w.group_size for w in srv.dispatch_log])
    log("resized", max_rel_err=_worst(zip(resized, refs)),
        bitwise_eq_unsharded=all(
            np.array_equal(a, b) for a, b in zip(resized, want)),
        max_diff_unsharded=_max_diff(resized, want))

    scale = max(float(np.max(np.abs(w))) for w in want)
    used = {d for s in mesh_sets + group_sets for d in s}
    return (len(used) == n_devices
            and all(len(s) == n_devices for s in mesh_sets)
            and _max_diff(got, want) <= tol * scale
            and _max_diff(resized, want) <= tol * scale
            and _worst(zip(got, refs)) <= tol
            and _worst(zip(resized, refs)) <= tol)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded and resized serving paths")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform!r}, not a TPU; "
              f"nothing run", file=sys.stderr)
        return 2
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {jax.device_count()} "
              f"devices", file=sys.stderr)
        return 2

    from repro.jax_cache import cache_entries, configure_compile_cache
    from repro.kernels import ops

    cache = configure_compile_cache()
    log("cache", dir=cache, entries_at_start=cache_entries(cache))
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=jax.device_count())
    if ops.default_interpret():
        print("chip_smoke: Pallas would run in interpret mode on a TPU",
              file=sys.stderr)
        return 1
    if args.chips == 4:
        results = {"sharded": sharded(n_devices=4)}
    else:
        results = {"whole_graph": whole_graph(), "serving": serving()}
    log("cache", dir=cache, entries_at_end=cache_entries(cache))
    failed = sorted(k for k, ok in results.items() if not ok)
    if failed:
        print(f"chip_smoke: outside tolerance: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.exit(main())
