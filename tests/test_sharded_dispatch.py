"""Device-sharded wave dispatch: mesh parity, LPT binning, lanes.

The sharding contract (DESIGN.md section 12):

* ``FusedModelExecutor.run_batch`` on a ``cores`` mesh is bitwise-
  identical to the unsharded program -- sharding splits the Alg. 8 task
  queue over devices (chips as Computation Cores), never the numerics --
  including on a 1-device mesh, where the shard_map program collapses to
  the single-lane scan;
* the jit trace count stays <= one per (shape bucket, lane count);
* request->slot placement (cost-aware LPT bins over perf_model costs)
  is a pure load-balance decision: any placement yields the same
  per-request outputs (request isolation);
* the multi-lane continuous scheduler keeps the single-lane bitwise
  parity with ``run_naive`` and records a valid pulling lane per wave.

Tests needing a real multi-device mesh skip unless 8 devices are visible
-- the CI ``multidevice`` job provides them via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` -- and ONE
subprocess smoke keeps the 8-device path covered in tier-1 too (same
pattern as ``tests/test_distributed.py``).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.distributed import sharding
from repro.serving.graph_engine import GraphServeEngine, random_requests
from repro.serving.scheduler import ContinuousGraphServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F_IN, HIDDEN, CLASSES = 16, 8, 5

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (CI multidevice tier sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _engine(mesh=None, slots=4, **kw):
    kw.setdefault("min_bucket", 32)
    return GraphServeEngine("gcn", f_in=F_IN, hidden=HIDDEN,
                            n_classes=CLASSES, slots=slots, mesh=mesh, **kw)


def _reqs(n=6, seed=2, sizes=(20, 52)):
    return random_requests(n, f_in=F_IN, sizes=sizes, seed=seed)


def test_one_device_mesh_bitwise_parity():
    """The sharded program on a 1-device mesh returns bit-for-bit the
    unsharded engine's outputs (the acceptance contract's base case)."""
    plain = _engine()
    meshed = _engine(mesh=sharding.cores_mesh(1))
    reqs = _reqs()
    for p, m in zip(plain.serve(reqs), meshed.serve(reqs)):
        assert p.request_id == m.request_id
        np.testing.assert_array_equal(p.logits, m.logits)


def test_slot_layout_is_cost_balanced_permutation():
    """Multi-lane slot placement: a permutation into per-lane ranges, at
    most slots/lanes per lane, deterministic -- exercised by forcing the
    lane count (placement logic is mesh-independent)."""
    eng = _engine(slots=8)
    eng.lanes = 4                   # placement path only; no mesh dispatch
    reqs = _reqs(7)
    layout = eng._slot_layout(reqs)
    assert sorted(set(layout)) == sorted(layout)      # distinct slots
    per_lane = [sum(1 for s in layout if s // 2 == lane)
                for lane in range(4)]
    assert max(per_lane) <= 2
    assert eng._slot_layout(reqs) == layout           # deterministic


def test_slot_placement_never_changes_numerics():
    """Request isolation: an engine with a permuted (multi-lane) slot
    layout still matches the FIFO-layout engine bitwise -- placement is
    load balance, not numerics.  Runs the real dispatch path on one
    device."""
    fifo = _engine(slots=4)
    permuted = _engine(slots=4)
    permuted.lanes = 2              # permute slots; mesh stays None
    reqs = _reqs(5)
    for a, b in zip(fifo.serve(reqs), permuted.serve(reqs)):
        np.testing.assert_array_equal(a.logits, b.logits)


def test_request_cost_tracks_density_and_size():
    """The perf_model request cost is monotone in what Alg. 8 balances:
    more vertices / denser graphs cost more; an empty graph costs 0."""
    eng = _engine()
    rng = np.random.default_rng(0)

    def req(n, dens):
        a = (rng.random((n, n)) < dens).astype(np.float32)
        h = (rng.random((n, F_IN)) < 0.5).astype(np.float32)
        from repro.serving.graph_engine import GraphRequest
        return GraphRequest(a, h)

    small, big = eng.request_cost(req(16, 0.3)), eng.request_cost(req(48, 0.3))
    assert big > small > 0.0
    sparse, dense = eng.request_cost(req(32, 0.05)), eng.request_cost(req(32, 0.9))
    assert dense >= sparse
    from repro.serving.graph_engine import GraphRequest
    empty = GraphRequest(np.zeros((8, 8), np.float32),
                         np.zeros((8, F_IN), np.float32))
    assert eng.request_cost(empty) == 0.0


def test_wave_loads_recorded():
    """Every dispatch appends its (real, slots) occupancy -- the series
    the serving benchmark's padding-efficiency column reads."""
    eng = _engine(slots=3)
    reqs = _reqs(5, sizes=(20,))            # one bucket: waves of 3 + 2
    eng.serve(reqs)
    assert eng.wave_loads == [(3, 3), (2, 3)]
    assert sum(r for r, _ in eng.wave_loads) == eng.served


def test_multilane_wait_bound_never_exceeds_serial():
    """The LPT-over-lanes wait bound equals the serial sum with one lane
    and can only shrink with more: concurrent lanes absorb other buckets'
    cut waves."""
    eng = _engine(slots=2)
    serial = ContinuousGraphServer(eng, n_lanes=1)
    wide = ContinuousGraphServer(eng, n_lanes=4)
    for srv in (serial, wide):
        for r in _reqs(3, sizes=(20, 52, 100)):   # 3 buckets, queued only
            srv.submit(r, deadline=srv.clock() + 1e6)
    for bucket in list(serial._queues):
        assert wide.wait_bound(bucket) <= serial.wait_bound(bucket) + 1e-12
    # one lane reproduces the serial-lane bound exactly: own + others
    some = next(iter(serial._queues))
    others = sum(serial.estimate(b) for b, q in serial._queues.items()
                 if b != some and q)
    assert serial.wait_bound(some) == pytest.approx(
        (serial.estimate(some) + others) * serial.slack_margin)


def test_invalid_mesh_and_slots_rejected():
    """slots must divide over the mesh's devices; run_batch rejects meshes
    that are not 1-D over the cores axis; cores_mesh rejects impossible
    device counts."""

    class TwoDeviceMeshStub:            # engine init only reads devices.size
        class devices:
            size = 2

    with pytest.raises(ValueError, match="not divisible"):
        _engine(mesh=TwoDeviceMeshStub(), slots=3)
    mesh = jax.make_mesh((1,), ("notcores",), axis_types=(AxisType.Auto,))
    eng = _engine(mesh=mesh, slots=4)
    with pytest.raises(ValueError, match="cores"):
        eng.serve(_reqs(1))
    with pytest.raises(ValueError):
        sharding.cores_mesh(10 ** 6)


@multidevice
def test_eight_device_mesh_bitwise_parity():
    """8 emulated host devices: the sharded wave dispatch (LPT-binned
    slots, one scan per device) matches ``run_naive`` AND the unsharded
    engine bitwise across mixed-size requests."""
    mesh = sharding.cores_mesh(8)
    meshed = _engine(mesh=mesh, slots=8)
    plain = _engine(slots=8)
    reqs = _reqs(11)
    sharded = meshed.serve(reqs)
    naive = {r.request_id: r for r in meshed.run_naive(reqs)}
    unsharded = {r.request_id: r for r in plain.serve(reqs)}
    for res in sharded:
        np.testing.assert_array_equal(res.logits,
                                      naive[res.request_id].logits)
        np.testing.assert_array_equal(res.logits,
                                      unsharded[res.request_id].logits)
    assert meshed.last_wave_report.wave_lanes == 8


@multidevice
def test_one_trace_per_bucket_per_lane_count():
    """Trace growth stays <= one per (shape bucket, lane count): repeated
    sharded serving re-traces only when a NEW bucket appears, and the
    sharded and unsharded programs for one bucket are distinct entries."""
    mesh = sharding.cores_mesh(8)
    eng = _engine(mesh=mesh, slots=8)
    reqs = _reqs(10)
    eng.serve(reqs)
    n_buckets = len(eng.buckets)
    traces = eng.executor.trace_count
    assert traces <= n_buckets
    eng.serve(reqs)                         # steady state: no new traces
    eng.serve(list(reversed(reqs)))
    assert eng.executor.trace_count == traces


@multidevice
def test_multilane_continuous_parity_and_lanes():
    """Multi-lane continuous serving on the 8-device mesh: bitwise ==
    run_naive, every wave pulled by a valid lane, every submission
    dispatched exactly once."""
    mesh = sharding.cores_mesh(8)
    eng = _engine(mesh=mesh, slots=8)
    srv = ContinuousGraphServer(eng, max_wait=0.0)     # n_lanes defaults 8
    assert srv.n_lanes == 8
    reqs = _reqs(9)
    done = []
    for r in reqs:
        srv.submit(r)
        done += srv.poll()
    done += srv.drain()
    assert srv.dispatched == srv.submitted == len(reqs)
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    for res in done:
        np.testing.assert_array_equal(res.logits,
                                      naive[res.request_id].logits)
    assert all(0 <= w.lane < srv.n_lanes for w in srv.dispatch_log)


def _random_partition(rng, n=8):
    """Random exact-cover group sizes for an n-device mesh: power-of-two
    sizes (they divide the engine's slots), summing to n."""
    sizes, left = [], n
    while left:
        choices = [s for s in (1, 2, 4, 8) if s <= left]
        s = int(rng.choice(choices))
        sizes.append(s)
        left -= s
    rng.shuffle(sizes)
    return sizes


@multidevice
@pytest.mark.parametrize("model", ["gcn", "sage", "gin", "sgc"])
def test_submesh_parity_fuzz_all_models(model):
    """Fuzz the disjoint-submesh dispatch for every GNN in the zoo:
    random group-size partitions and random request orders, every wave
    bitwise equal to the unsharded ``run_naive`` oracle.  Group choice is
    load balance, NEVER numerics -- the acceptance contract of the
    submesh tentpole."""
    mesh = sharding.cores_mesh(8)
    eng = GraphServeEngine(model, f_in=F_IN, hidden=HIDDEN,
                           n_classes=CLASSES, slots=8, min_bucket=32,
                           mesh=mesh)
    reqs = _reqs(8, seed=7, sizes=(20, 28))     # one bucket, full wave
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    rng = np.random.default_rng(11)
    for round_ in range(3):
        order = list(reqs)
        rng.shuffle(order)
        for sub in sharding.partition_mesh(mesh, _random_partition(rng)):
            results = eng.finish_wave(eng.begin_wave(32, order, submesh=sub))
            for res in results:
                np.testing.assert_array_equal(
                    res.logits, naive[res.request_id].logits,
                    err_msg=f"{model} round {round_} group "
                            f"{sub.devices.size} req {res.request_id}")
    # trace bound: one program per (bucket, distinct group size)
    assert eng.executor.trace_count <= 1 + 4    # naive bucket + sizes<=4


@multidevice
def test_resize_midstream_parity():
    """Mid-stream resize events: the continuous server replans its device
    groups between waves as queue composition shifts (different bucket
    mixes per tick), and every result stays bitwise equal to run_naive."""
    mesh = sharding.cores_mesh(8)
    eng = _engine(mesh=mesh, slots=8)
    srv = ContinuousGraphServer(eng, max_wait=0.0, resize=True)
    rng = np.random.default_rng(5)
    reqs = _reqs(14, seed=9, sizes=(20, 52, 100))   # 3 buckets
    order = list(reqs)
    rng.shuffle(order)
    done, plans = [], []
    for i, r in enumerate(order):
        srv.submit(r)
        if i % 3 == 2:                      # varying queue mixes per tick
            done += srv.poll()
            plans.append(tuple(srv.last_group_sizes))
    done += srv.drain()
    plans.append(tuple(srv.last_group_sizes))
    assert srv.dispatched == srv.submitted == len(reqs)
    assert len(set(plans)) > 1, f"no resize events observed: {plans}"
    naive = {r.request_id: r for r in eng.run_naive(reqs)}
    for res in done:
        np.testing.assert_array_equal(res.logits,
                                      naive[res.request_id].logits)
    # every wave ran on a real group of the tick's plan
    assert all(w.group_size in (1, 2, 4, 8) for w in srv.dispatch_log)


@pytest.mark.skipif(
    jax.device_count() >= 8,
    reason="redundant where the in-process @multidevice tests already run")
def test_subprocess_eight_device_smoke():
    """Tier-1 coverage of the REAL 8-device path: a fresh interpreter with
    forced host devices runs a minimal sharded-vs-naive parity check (the
    in-process 8-device tests above only run in the multidevice CI job,
    where this subprocess duplicate skips itself)."""
    code = """
        import numpy as np
        from repro.distributed import sharding
        from repro.serving.graph_engine import GraphServeEngine, \\
            random_requests
        eng = GraphServeEngine("gcn", f_in=8, hidden=4, n_classes=3,
                               slots=8, min_bucket=16,
                               mesh=sharding.cores_mesh(8))
        reqs = random_requests(8, f_in=8, sizes=(12,), seed=5)
        served = eng.serve(reqs)
        naive = {r.request_id: r for r in eng.run_naive(reqs)}
        for r in served:
            assert np.array_equal(r.logits, naive[r.request_id].logits)
        assert eng.executor.trace_count == len(eng.buckets) == 1
        assert eng.last_wave_report.wave_lanes == 8
        print("sharded-parity-ok")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded-parity-ok" in out.stdout
