"""Property tests for disjoint submesh partitioning (DESIGN.md section 14).

The submesh layer has two pure policy functions and one trace-sharing
contract, all pinned property-style:

* ``distributed.sharding.partition_devices`` is an EXACT COVER: every
  device lands in exactly one group, order preserved, and any non-cover
  (sum != N, zero/negative size, no groups) raises ``ValueError``;
* ``serving.scheduler.plan_groups`` always emits a valid partition whose
  sizes divide the wave slots, respects ``max_groups``, pairs the widest
  group with the largest demand, and is deterministic;
* equal-size groups share ONE compiled program: dispatching the same
  bucket over disjoint same-size submeshes grows
  ``FusedModelExecutor.trace_count`` by at most the number of DISTINCT
  group sizes (the runtime traces against the abstract cores mesh).

Each property is a plain checker function; hypothesis drives them with
arbitrary draws when it is installed (CI), and a seeded random sweep
drives the same checkers otherwise (this container).  The trace-sharing
contract needs 8 devices (multidevice CI tier) and keeps tier-1 coverage
through one subprocess smoke, the ``test_sharded_dispatch.py`` pattern.
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
from repro.serving.scheduler import plan_groups

from conftest import HAVE_HYPOTHESIS, given, settings, st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (CI multidevice tier sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


# -- checkers (shared by hypothesis and the seeded fallback) ----------------

def check_partition_exact_cover(group_sizes):
    """Every device in exactly one group, order preserved, sizes honored.
    Devices are plain ints here: partition_devices is pure sequence
    logic, identical for jax Device objects."""
    n = sum(group_sizes)
    devices = list(range(n))
    groups = sharding.partition_devices(devices, group_sizes)
    assert [len(g) for g in groups] == list(group_sizes)
    flat = [d for g in groups for d in g]
    assert flat == devices                      # cover + order, no overlap


def check_invalid_partitions_raise(group_sizes):
    """Any non-exact-cover raises: short sum, long sum, a zero-size group,
    a negative group, and the empty partition."""
    n = sum(group_sizes)
    devices = list(range(n))
    with pytest.raises(ValueError, match="sum"):
        sharding.partition_devices(devices + [n], group_sizes)
    with pytest.raises(ValueError, match="sum"):
        sharding.partition_devices(devices, list(group_sizes) + [1])
    with pytest.raises(ValueError, match=">= 1"):
        sharding.partition_devices(devices + [n], [0] + list(group_sizes))
    with pytest.raises(ValueError, match=">= 1"):
        sharding.partition_devices(devices, [-1, 1] + list(group_sizes))
    with pytest.raises(ValueError, match="zero groups"):
        sharding.partition_devices([], [])


def check_plan_groups(n_devices, demands, slots, max_groups):
    """plan_groups emits a valid exact-cover partition: positive sizes,
    each dividing ``slots``, summing to ``n_devices``; at most
    ``min(len(demands), n_devices, max_groups)`` demand-assigned groups
    (the rest are idle 1-device groups); sizes descending (widest group
    pairs with the largest demand); deterministic."""
    sizes = plan_groups(n_devices, demands, slots, max_groups=max_groups)
    assert sum(sizes) == n_devices
    assert all(s >= 1 for s in sizes)
    assert all(slots % s == 0 for s in sizes)
    assert sizes == sorted(sizes, reverse=True)
    k = min(len(demands), n_devices,
            n_devices if max_groups is None else max_groups)
    # trailing entries beyond the k demand-assigned groups are idle 1s
    assert all(s == 1 for s in sizes[k:])
    assert sizes == plan_groups(n_devices, demands, slots,
                                max_groups=max_groups)


# -- hypothesis drivers (CI; skipped where hypothesis is absent) ------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(group_sizes=st.lists(st.integers(1, 9), min_size=1, max_size=10))
    def test_partition_exact_cover_property(group_sizes):
        check_partition_exact_cover(group_sizes)

    @settings(max_examples=40, deadline=None)
    @given(group_sizes=st.lists(st.integers(1, 9), min_size=1, max_size=6))
    def test_invalid_partitions_raise_property(group_sizes):
        check_invalid_partitions_raise(group_sizes)

    @settings(max_examples=80, deadline=None)
    @given(n_devices=st.integers(1, 16),
           demands=st.lists(st.floats(0.0, 1e3), min_size=1, max_size=10),
           slots_per_device=st.integers(1, 4),
           max_groups=st.one_of(st.none(), st.integers(1, 16)))
    def test_plan_groups_property(n_devices, demands, slots_per_device,
                                  max_groups):
        # slots a multiple of a power of two >= n_devices, the engine's
        # own divisibility regime (slots % mesh size == 0)
        slots = slots_per_device * (1 << (n_devices - 1).bit_length())
        check_plan_groups(n_devices, demands, slots, max_groups)


# -- seeded fallback sweep (always runs; same checkers) ---------------------

@pytest.mark.parametrize("seed", range(10))
def test_partition_exact_cover_sweep(seed):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 9, size=rng.integers(1, 10)).tolist()
    check_partition_exact_cover(sizes)
    check_invalid_partitions_raise(sizes)


@pytest.mark.parametrize("seed", range(10))
def test_plan_groups_sweep(seed):
    rng = np.random.default_rng(200 + seed)
    n_devices = int(rng.integers(1, 16))
    demands = rng.random(rng.integers(1, 10)).tolist()
    slots = int(rng.integers(1, 4)) * (1 << (n_devices - 1).bit_length())
    max_groups = None if seed % 2 else int(rng.integers(1, 16))
    check_plan_groups(n_devices, demands, slots, max_groups)


# -- pinned policy examples -------------------------------------------------

def test_plan_groups_pinned_examples():
    """The resize-policy shapes the scheduler tests rely on: a lone wave
    takes the whole mesh, a huge wave grabs a wide group while small waves
    pack one device each, equal demands split evenly, and ``max_groups=1``
    is always the single full-mesh group."""
    assert plan_groups(8, [1.0], 8) == [8]
    assert plan_groups(8, [10.0, .1, .1, .1, .1], 8) == [4, 1, 1, 1, 1]
    assert plan_groups(8, [1.0] * 5, 8) == [2, 2, 2, 1, 1]
    assert plan_groups(8, [1.0, 2.0, 3.0], 8, max_groups=1) == [8]
    # more demands than devices: one device each, extras wait
    assert plan_groups(4, [1.0] * 9, 8) == [1, 1, 1, 1]


def test_plan_groups_invalid_inputs_raise():
    with pytest.raises(ValueError, match="devices"):
        plan_groups(0, [1.0], 8)
    with pytest.raises(ValueError, match="slots"):
        plan_groups(8, [1.0], 0)
    with pytest.raises(ValueError, match="no demands"):
        plan_groups(8, [], 8)
    with pytest.raises(ValueError, match="negative"):
        plan_groups(8, [1.0, -2.0], 8)
    with pytest.raises(ValueError, match="max_groups"):
        plan_groups(8, [1.0], 8, max_groups=0)


def test_partition_mesh_validates_axis_and_single_device():
    """partition_mesh demands a 1-D cores mesh; the 1-device partition
    (tier-1's whole visible world) round-trips."""
    with pytest.raises(ValueError, match="cores"):
        mesh = jax.make_mesh((1,), ("notcores",),
                             axis_types=(AxisType.Auto,))
        sharding.partition_mesh(mesh, [1])
    [sub] = sharding.partition_mesh(sharding.cores_mesh(1), [1])
    assert sub.devices.size == 1
    assert sub.axis_names == (sharding.CORES_AXIS,)


def test_abstract_cores_mesh_shape():
    am = sharding.abstract_cores_mesh(4)
    assert am.shape[sharding.CORES_AXIS] == 4
    with pytest.raises(ValueError):
        sharding.abstract_cores_mesh(0)


# -- trace sharing across equal-size groups (8 devices) ---------------------

@multidevice
def test_equal_size_groups_share_one_program():
    """Dispatching one bucket over DISJOINT same-size submeshes compiles
    ONE program: trace growth <= the number of distinct group sizes, and
    the later groups are pure cache hits (the runtime keys its program
    cache on the group SIZE via the abstract cores mesh)."""
    from repro.serving.graph_engine import GraphServeEngine, random_requests

    mesh = sharding.cores_mesh(8)
    eng = GraphServeEngine("gcn", f_in=8, hidden=4, n_classes=3, slots=8,
                           min_bucket=16, mesh=mesh)
    reqs = random_requests(8, f_in=8, sizes=(12,), seed=3)
    sub4a, sub4b = sharding.partition_mesh(mesh, [4, 4])
    for sub in (sub4a, sub4b):
        eng.finish_wave(eng.begin_wave(16, reqs, submesh=sub))
    assert eng.executor.trace_count == 1        # one (bucket, size-4) trace
    misses = eng.executor.cache_misses
    # a mixed partition adds exactly the sizes not yet seen (2 and 1)
    for sub in sharding.partition_mesh(mesh, [4, 2, 1, 1]):
        eng.finish_wave(eng.begin_wave(16, reqs, submesh=sub))
    assert eng.executor.trace_count == 3        # sizes {4, 2, 1}
    assert eng.executor.cache_misses == misses + 2
    assert sorted(eng.group_walls) == [1, 2, 4]
    # equal-size walls recorded once per dispatched group
    assert len(eng.group_walls[4]) == 3 and len(eng.group_walls[1]) == 2


@pytest.mark.skipif(
    jax.device_count() >= 8,
    reason="redundant where the in-process @multidevice tests already run")
def test_subprocess_trace_sharing_smoke():
    """Tier-1 coverage of the real equal-size trace-sharing contract in a
    fresh 8-device interpreter (the in-process test above only runs in the
    multidevice CI job)."""
    code = """
        import numpy as np
        from repro.distributed import sharding
        from repro.serving.graph_engine import GraphServeEngine, \\
            random_requests
        mesh = sharding.cores_mesh(8)
        eng = GraphServeEngine("gcn", f_in=8, hidden=4, n_classes=3,
                               slots=8, min_bucket=16, mesh=mesh)
        reqs = random_requests(8, f_in=8, sizes=(12,), seed=3)
        outs = []
        for sub in sharding.partition_mesh(mesh, [4, 4]):
            res = eng.finish_wave(eng.begin_wave(16, reqs, submesh=sub))
            outs.append([r.logits for r in res])
        assert eng.executor.trace_count == 1, eng.executor.trace_count
        for a, b in zip(*outs):
            assert np.array_equal(a, b)
        naive = {r.request_id: r for r in eng.run_naive(reqs)}
        for res, req in zip(outs[0], reqs):
            assert np.array_equal(res, naive[req.request_id].logits)
        print("submesh-trace-sharing-ok")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "submesh-trace-sharing-ok" in out.stdout
