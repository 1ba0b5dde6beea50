"""Batched GNN serving: concurrent graph queries over one compiled model.

This is the repo's north-star serving system (ROADMAP "Batched GNN
serving"): the paper's runtime exists to serve a *stream* of inference
queries -- the soft processor profiles each incoming graph's sparsity and
re-plans the kernel-to-primitive mapping per input (Algorithm 8's task
queue fed per query).  :class:`GraphServeEngine` realizes that loop on top
of the fused whole-model executor:

    request -> shape bucket -> admission wave -> profile -> plan -> execute

* **Shape bucketing + pad-to-bucket.**  Every request carries its own
  adjacency/features (its own vertex count, its own density profile).
  Requests are admitted in waves of ``slots`` whose padded vertex count is
  rounded up to a power-of-two bucket, mirroring ``serving.engine
  .ServeEngine``'s slot admission for LM sequences.  One ``CompiledModel``
  per bucket (Algorithm 9 partitioning at the bucket size) is shared by
  every request that lands in it; model weights are shared globally
  (``models.gnn.init_spec_weights`` -- weight shapes never depend on |V|).

* **One jit trace per shape bucket.**  A wave executes as ONE dispatch of
  `core.runtime.FusedModelExecutor`'s batched program (``run_batch``): a
  ``lax.scan`` over the stacked per-request tensors whose body is the PR-2
  chained-writeback walk, unchanged -- each request's K2P codes are planned
  from ITS profile, layer l+1 from layer l's writeback counts.  Waves are
  padded to a fixed ``slots`` with zero dummy requests (their blocks plan
  to SKIP), so the program signature -- and hence the trace -- is unique
  per bucket.  Steady-state waves are pure cache hits with
  ``donate_argnums`` buffer reuse: no re-trace, no host re-profiling of
  the shared weights.

* **Bitwise request isolation.**  A request's computation depends only on
  its own slice of the wave and the shared weights, so outputs are
  bitwise-identical to the request served alone -- one single-inference
  ``FusedModelExecutor.run`` on the same padded tensors
  (:meth:`GraphServeEngine.run_naive` is that oracle) -- regardless of
  admission order or wave composition --
  ``tests/test_graph_serving.py`` pins both properties for the whole
  model zoo.

`benchmarks/bench_serving.py` measures the two paths (p50/p99 latency,
throughput) and gates CI on the batched path staying ahead.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import spans
from repro.core import compiler, runtime
from repro.core import scheduler as core_scheduler
from repro.core.compiler import CompiledModel, GraphMeta
from repro.core.perf_model import Primitive
from repro.data import graphs as graph_data
from repro.models import gnn as gnn_models
from repro.serving.config import UNSET, EngineConfig, merge_config


@dataclasses.dataclass
class GraphRequest:
    """One inference query: a graph at the engine's feature width.

    ``adjacency`` is the raw (n, n) 0/1 adjacency (self loops optional --
    the engine forces them during normalization, like ``data.graphs
    .materialize``); ``features`` is the (n, f_in) node feature matrix.
    """

    adjacency: np.ndarray
    features: np.ndarray
    request_id: int = 0

    @property
    def n_vertices(self) -> int:
        return int(self.features.shape[0])


@dataclasses.dataclass
class GraphResult:
    request_id: int
    logits: np.ndarray              # (n, n_classes), padding rows sliced off
    bucket: int                     # padded vertex count the wave ran at
    wave: int                       # admission wave index (diagnostics)
    # continuous-serving metadata (serving.scheduler fills these in;
    # the synchronous serve()/run_naive() paths leave them None)
    deadline: Optional[float] = None      # absolute clock deadline, if any
    completed_at: Optional[float] = None  # clock time the wave finished

    @property
    def deadline_met(self) -> Optional[bool]:
        """True/False under the continuous scheduler; None when the result
        came from a path with no deadline accounting."""
        if self.deadline is None or self.completed_at is None:
            return None
        return self.completed_at <= self.deadline


@dataclasses.dataclass
class InFlightWave:
    """A launched-but-unfinished admission wave (``begin_wave``'s handle):
    the requests, their slot placement, and the executor's pending
    dispatch.  Pass to ``finish_wave`` to block and collect results."""

    bucket: int
    wave: List[GraphRequest]
    slot_of: List[int]
    pending: runtime.PendingWave
    final: str                      # env name of the model's output tensor
    index: int                      # admission wave index (GraphResult.wave)


def random_requests(n_requests: int, *, f_in: int,
                    sizes: Sequence[int] = (48, 96, 160),
                    seed: int = 0, avg_degree: int = 8,
                    feat_density: float = 0.25) -> List[GraphRequest]:
    """A synthetic query stream with per-request size AND sparsity.

    Each request draws its own vertex count (jittered around ``sizes``),
    power-law degree structure, and feature density, so every admitted
    graph carries a distinct density profile -- the property the
    per-request K2P re-planning exploits.  Used by the serving tests,
    benchmark, and example.
    """
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        base = int(rng.choice(np.asarray(sizes)))
        n = max(8, base - int(rng.integers(0, max(base // 4, 1))))
        e = max(n * avg_degree, n)
        w = graph_data.powerlaw_marginal(n, rng)
        src = rng.choice(n, size=e, p=w)
        dst = rng.choice(n, size=e, p=w)
        a = np.zeros((n, n), np.float32)
        a[src, dst] = 1.0
        a[dst, src] = 1.0
        dens = float(np.clip(feat_density * rng.uniform(0.4, 1.6), 0.02, 1.0))
        mask = rng.random((n, f_in)) < dens
        h = (rng.normal(size=(n, f_in)).astype(np.float32) ** 2) * mask
        out.append(GraphRequest(a, h, request_id=i))
    return out


class GraphServeEngine:
    """Request-loop GNN server over one shared compiled model per bucket.

    Construct once per deployed model (``model``/``f_in``/``hidden``/
    ``n_classes`` fix the spec; weights are built by
    ``models.gnn.init_spec_weights`` or passed in), then call
    :meth:`serve` with any mix of :class:`GraphRequest` sizes:

    >>> eng = GraphServeEngine("gcn", f_in=64, n_classes=7)
    >>> results = eng.serve(random_requests(8, f_in=64))

    Contracts:

    * results come back in request order, each sliced to its request's
      true vertex count;
    * outputs are bitwise-identical to :meth:`run_naive` (one
      single-inference program per request on the same padded tensors)
      and invariant to admission order;
    * ``executor.trace_count`` grows by at most one per shape bucket --
      waves are padded to ``slots`` requests so the batched program
      signature is unique per bucket;
    * ``collect_report=False`` (the default) skips ALL per-request host
      bookkeeping on the serving path; flip it on for debugging and the
      wave report carries per-request per-kernel entries.

    ``min_bucket`` floors the bucket ladder (buckets are the next power of
    two >= the request's vertex count); ``align`` follows the test-scale
    partitioning convention of ``models.gnn.build_dense``.

    The knobs consolidate into :class:`~repro.serving.config.EngineConfig`
    (``config=`` / :meth:`from_config`; the resolved config is kept on
    ``self.config``).  Every historical kwarg keeps working: explicit
    kwargs override default-valued config fields, and a kwarg conflicting
    with a field the config explicitly sets raises (serving.config's
    ``merge_config`` rule, DESIGN.md section 15).

    ``mesh`` (a 1-D ``cores`` mesh, ``distributed.sharding.cores_mesh``)
    device-shards every wave: requests are LPT-binned into per-device
    slot ranges by predicted cost (:meth:`request_cost`) and each device
    scans its own range (DESIGN.md section 12).  Outputs stay bitwise
    identical -- on any mesh -- and the trace bound becomes one per
    (bucket, group size); ``slots`` must divide by the mesh's device
    count.  :meth:`begin_wave` additionally takes a per-wave ``submesh``
    (a disjoint device group from ``distributed.sharding
    .partition_mesh``), placing the wave's requests within that group
    only -- the disjoint-lane dispatch of DESIGN.md section 14; programs
    are shared across equal-size groups, so resizing groups between waves
    never re-traces.
    """

    def __init__(self, model: str = UNSET, *,
                 config: Optional[EngineConfig] = None,
                 f_in: int = UNSET, hidden: int = UNSET,
                 n_classes: int = UNSET,
                 weights: Optional[Dict[str, np.ndarray]] = UNSET,
                 weight_seed: int = UNSET, weight_density: float = UNSET,
                 slots: int = UNSET, min_bucket: int = UNSET,
                 strategy: str = UNSET, n_cc: int = UNSET, align: int = UNSET,
                 on_chip_bytes: int = UNSET,
                 donate: bool = UNSET, collect_report: bool = UNSET,
                 keep_codes: bool = UNSET, mesh: Optional[Mesh] = UNSET,
                 cost_model=UNSET, format_aware: bool = UNSET,
                 csr_rmax: int = UNSET):
        # every historical kwarg still works; ``config=`` supplies the
        # consolidated base and ``merge_config`` arbitrates (explicit
        # kwargs override default-valued config fields, conflicting
        # duplicates raise -- serving.config, DESIGN.md section 15)
        cfg = merge_config(EngineConfig, config, dict(
            model=model, f_in=f_in, hidden=hidden, n_classes=n_classes,
            weights=weights, weight_seed=weight_seed,
            weight_density=weight_density, slots=slots,
            min_bucket=min_bucket, strategy=strategy, n_cc=n_cc,
            align=align, on_chip_bytes=on_chip_bytes, donate=donate,
            collect_report=collect_report, keep_codes=keep_codes,
            mesh=mesh, cost_model=cost_model, format_aware=format_aware,
            csr_rmax=csr_rmax)).validate()
        self.config = cfg
        model, f_in, hidden, n_classes = (cfg.model, cfg.f_in, cfg.hidden,
                                          cfg.n_classes)
        weights, slots, mesh = cfg.weights, cfg.slots, cfg.mesh
        self.spec = gnn_models.make_model_spec(model, f_in, hidden, n_classes)
        self.f_in = f_in
        self.slots = slots
        # device-sharded dispatch (DESIGN.md section 12): a 1-D ``cores``
        # mesh splits every wave's slots evenly over its devices -- chips
        # as the paper's Computation Cores.  Requests are placed into each
        # device's slot range by cost-aware LPT bins
        # (``core.scheduler.assign_bins`` over per-request perf_model
        # costs) so the per-device scans finish together.
        self.mesh = mesh
        self.lanes = 1 if mesh is None else int(mesh.devices.size)
        if slots % self.lanes:
            raise ValueError(
                f"slots={slots} not divisible by the {self.lanes}-device "
                f"cores mesh")
        # keep the documented pad-to-pow2 contract whatever floor is passed
        self.min_bucket = 1 << (max(cfg.min_bucket, 2) - 1).bit_length()
        self.strategy = cfg.strategy
        self.n_cc = cfg.n_cc
        self.align = cfg.align
        self.on_chip_bytes = cfg.on_chip_bytes
        if weights is None:
            weights = gnn_models.init_spec_weights(
                self.spec, seed=cfg.weight_seed, density=cfg.weight_density)
        # one jnp array per weight, held for the engine's lifetime: the
        # executor's input-profile cache is identity-keyed, so steady-state
        # waves never re-profile them on the host.
        self.weights = {name: jnp.asarray(w) for name, w in weights.items()}
        # cost_model picks the K2P/format model (None -> the paper-faithful
        # FPGACostModel; pass perf_model.TPUCostModel() to turn on row-CSR
        # format decisions, DESIGN.md section 13).  format_aware/csr_rmax
        # thread through to BOTH the serving executor and run_naive's
        # executor, so format decisions stay part of the bitwise
        # serve == run_naive contract.
        self.executor = runtime.FusedModelExecutor(
            strategy=cfg.strategy, model=cfg.cost_model, n_cc=cfg.n_cc,
            donate=cfg.donate, collect_report=cfg.collect_report,
            keep_codes=cfg.keep_codes, format_aware=cfg.format_aware,
            csr_rmax=cfg.csr_rmax)
        # run_naive's single-inference executor.  Never donating: its
        # inputs include ``self.weights``, which every wave reads.
        self._naive = runtime.FusedModelExecutor(
            strategy=cfg.strategy, model=self.executor.model, n_cc=cfg.n_cc,
            donate=False, format_aware=cfg.format_aware,
            csr_rmax=cfg.csr_rmax)
        self._compiled: Dict[int, CompiledModel] = {}
        self._input_names: Dict[int, List[str]] = {}
        # serving counters (benchmark/test observability)
        self.waves = 0
        self.served = 0
        self.wave_walls: List[float] = []
        # per-wave (real, slots) occupancy: the padding-efficiency series
        # the serving benchmark reports (real/slots per wave)
        self.wave_loads: List[Tuple[int, int]] = []
        # per-bucket dispatch walls: what the continuous scheduler's EWMA
        # wave-wall estimator seeds from (DESIGN.md section 11)
        self.bucket_walls: Dict[int, List[float]] = {}
        # per-group-size dispatch walls (key: the device-group size the
        # wave ran on; 1 when unsharded): the resize policy's per-size
        # lane-wall estimates seed from these (DESIGN.md section 14)
        self.group_walls: Dict[int, List[float]] = {}
        self.last_wave_report: Optional[runtime.InferenceReport] = None

    @classmethod
    def from_config(cls, config: EngineConfig) -> "GraphServeEngine":
        """Build an engine from a consolidated :class:`EngineConfig`.

        Round-trips: ``GraphServeEngine.from_config(eng.config)`` builds
        an equivalent engine (same spec, same generated weights -- weight
        generation is seeded -- same executor policy)."""
        return cls(config=config)

    # -- admission ----------------------------------------------------------
    def _validate(self, req: GraphRequest) -> None:
        for name, arr in (("adjacency", req.adjacency),
                          ("features", req.features)):
            a = np.asarray(arr)
            # admission casts to float32; anything that can't carry graph
            # numerics safely (complex, object, strings, ...) is rejected
            # here rather than exploding -- or worse, silently casting --
            # inside normalize_adjacency.
            if not (np.issubdtype(a.dtype, np.floating)
                    or np.issubdtype(a.dtype, np.integer)
                    or a.dtype == np.bool_):
                raise ValueError(
                    f"request {req.request_id}: {name} dtype {a.dtype} is "
                    f"not numeric (float/int/bool)")
            # NaN/inf would flow through normalize_adjacency's degree sums
            # and poison every request sharing the wave.
            if (np.issubdtype(a.dtype, np.floating)
                    and not np.isfinite(a).all()):
                raise ValueError(
                    f"request {req.request_id}: {name} contains non-finite "
                    f"values (NaN/inf)")
        if req.features.ndim != 2:
            raise ValueError(
                f"request {req.request_id}: features must be 2-D "
                f"(n_vertices, f_in), got shape {req.features.shape}")
        if req.features.shape[1] != self.f_in:
            raise ValueError(
                f"request {req.request_id}: feature width "
                f"{req.features.shape[1]} != engine f_in {self.f_in}")
        n = req.n_vertices
        if req.adjacency.shape != (n, n):
            raise ValueError(
                f"request {req.request_id}: adjacency "
                f"{req.adjacency.shape} != ({n}, {n}) for {n} feature rows")

    def bucket_for(self, n_vertices: int) -> int:
        """Smallest power-of-two >= max(n_vertices, min_bucket)."""
        b = self.min_bucket
        while b < n_vertices:
            b *= 2
        return b

    @property
    def buckets(self) -> List[int]:
        """Shape buckets compiled so far (one jit trace each)."""
        return sorted(self._compiled)

    def _compile(self, bucket: int) -> CompiledModel:
        cm = self._compiled.get(bucket)
        if cm is None:
            meta = GraphMeta(f"serve{bucket}", bucket, bucket * 8, self.f_in)
            cm = compiler.compile_model(
                self.spec, meta, n_cc=self.n_cc, align=self.align,
                on_chip_bytes=self.on_chip_bytes)
            self._compiled[bucket] = cm
            flows = runtime.FusedModelExecutor._resolved_flows(cm)
            self._input_names[bucket] = sorted(
                {f.source for pair in flows for f in pair or ()
                 if f.producer is None and f.source not in self.weights})
        return cm

    def _input_shape(self, name: str, bucket: int) -> Tuple[int, int]:
        if name in ("A", "A_mean"):
            return (bucket, bucket)
        if name == "H0":
            return (bucket, self.f_in)
        raise KeyError(f"no admission builder for graph input {name!r}")

    def _fill_slot(self, req: GraphRequest,
                   views: Dict[str, np.ndarray]) -> None:
        """Normalize-then-fill ONE request into zero-initialized slot
        views (one (bucket, ...) view per graph input).  Normalization
        sees the true graph -- padding vertices stay isolated, zero
        rows/cols -- so real-vertex outputs are untouched by the bucket
        size.  Feature rows fill via the request's ``fill_features`` hook
        when it has one (store-backed mini-batch requests gather straight
        from the pinned FeatureStore into the slot, DESIGN.md section 16)
        and a plain copy otherwise."""
        n = req.n_vertices
        adj = None
        for name, view in views.items():
            if name == "H0":
                fill = getattr(req, "fill_features", None)
                if fill is not None:
                    fill(view[:n])
                else:
                    view[:n] = np.asarray(req.features, np.float32)
            else:
                if adj is None:
                    adj = graph_data.normalize_adjacency(req.adjacency)
                view[:n, :n] = adj[0] if name == "A" else adj[1]

    def _padded(self, req: GraphRequest, bucket: int
                ) -> Dict[str, np.ndarray]:
        """One request's padded input dict, for exactly the graph inputs
        this bucket's compiled model consumes (``_input_names``, derived
        from the operand flows).  ``run_naive``'s admission path; the
        wave path fills slot views of one batched buffer instead
        (:meth:`begin_wave` over :meth:`_fill_slot`)."""
        self._compile(bucket)            # ensure _input_names is populated
        out = {name: np.zeros(self._input_shape(name, bucket), np.float32)
               for name in self._input_names[bucket]}
        self._fill_slot(req, out)
        return out

    def cut_wave(self, entries: Sequence, *, force: bool = False
                 ) -> Tuple[list, list]:
        """Cut at most one wave off the front of a FIFO of entries.

        Returns ``(wave, rest)``: the first ``slots`` entries when a full
        wave is available; the whole (short) remainder when ``force`` is set
        (a deadline-, age-, or drain-triggered partial wave); otherwise an
        empty wave and ``entries`` unchanged.  Pure -- the synchronous
        ``serve`` and the continuous scheduler share it, so every admission
        property (wave size <= slots, each request in exactly one wave)
        is pinned once.
        """
        entries = list(entries)
        if len(entries) >= self.slots:
            return entries[: self.slots], entries[self.slots:]
        if force and entries:
            return entries, []
        return [], entries

    def _admit(self, requests: Sequence[GraphRequest]
               ) -> Dict[int, List[List[Tuple[int, GraphRequest]]]]:
        """Group by bucket (first-seen order), then cut into waves of at
        most ``slots`` requests each (trailing partial waves forced)."""
        by_bucket: Dict[int, List[Tuple[int, GraphRequest]]] = {}
        for idx, req in enumerate(requests):
            self._validate(req)
            by_bucket.setdefault(self.bucket_for(req.n_vertices), []
                                 ).append((idx, req))
        out: Dict[int, List[List[Tuple[int, GraphRequest]]]] = {}
        for bucket, entries in by_bucket.items():
            waves = []
            while entries:
                wave, entries = self.cut_wave(entries, force=True)
                waves.append(wave)
            out[bucket] = waves
        return out

    # -- execution ----------------------------------------------------------
    def request_cost(self, req: GraphRequest) -> float:
        """Analyzer-predicted cost of one request (relative units).

        The perf_model Table IV cost of the request's dominant Aggregate
        product at its measured adjacency/feature densities -- the same
        model the K2P planner minimizes over, applied at request
        granularity.  Feeds ``core.scheduler.assign_bins`` so the sharded
        dispatch packs each mesh device an even predicted load (Algorithm
        8's cost-aware task->core assignment with requests as tasks).

        Memoized on the request object (requests are treated as immutable
        once validated at the admission edge), so re-serving one never
        re-scans its O(n^2) tensors on the dispatch path.  The memo is
        keyed by the engine's (cost model, f_in) -- a request shared
        between engines with different models is re-costed, not reused.
        """
        memo_key = (self.executor.model, self.f_in)
        cached = getattr(req, "_dynasparse_cost", None)
        if cached is not None and cached[0] == memo_key:
            return cached[1]
        adj = np.asarray(req.adjacency)
        feat = np.asarray(req.features)
        n = max(req.n_vertices, 1)
        d_adj = float(np.count_nonzero(adj)) / max(adj.size, 1)
        d_feat = float(np.count_nonzero(feat)) / max(feat.size, 1)
        model = self.executor.model
        prim = model.select(d_adj, d_feat)
        cost = (0.0 if prim == Primitive.SKIP else
                float(model.cycles(prim, n, n, self.f_in, d_adj, d_feat)))
        req._dynasparse_cost = (memo_key, cost)
        return cost

    def _slot_layout(self, wave: Sequence[GraphRequest],
                     lanes: Optional[int] = None) -> List[int]:
        """Request -> slot placement for one wave over ``lanes`` devices
        (default: the engine mesh's device count).

        Unsharded (or single-device) waves keep the FIFO layout.  On a
        multi-device group, device d owns the contiguous slot range
        ``[d*slots/lanes, (d+1)*slots/lanes)``; requests are LPT-binned
        over the per-request perf_model costs (capacity = each device's
        slot count) so every device's scan carries a balanced predicted
        load, and dummies fill whatever slots remain.  Placement never
        affects numerics (request isolation), only load balance.
        """
        lanes = self.lanes if lanes is None else lanes
        if lanes == 1:
            return list(range(len(wave)))
        per_lane = self.slots // lanes
        bins = core_scheduler.assign_bins(
            [self.request_cost(r) for r in wave], lanes,
            capacity=per_lane)
        next_slot = [lane * per_lane for lane in range(lanes)]
        slots = []
        for lane in bins:
            slots.append(next_slot[lane])
            next_slot[lane] += 1
        return slots

    def begin_wave(self, bucket: int, wave: Sequence[GraphRequest],
                   submesh: Optional[Mesh] = None) -> "InFlightWave":
        """Launch one admission wave WITHOUT blocking: pad each request to
        ``bucket`` (dummies fill the unused slots), place requests into
        slots by the cost-aware layout (:meth:`_slot_layout`), and hand the
        stacked tensors to ``FusedModelExecutor.launch_batch``.

        ``submesh`` dispatches THIS wave on a specific device group (a
        disjoint submesh from ``distributed.sharding.partition_mesh``)
        instead of the engine's full mesh: requests are placed within the
        group's slot ranges only, and the wave executes on the group's
        devices alone -- the per-lane disjoint dispatch the resize-capable
        continuous scheduler drives (DESIGN.md section 14).  ``slots``
        must divide by the group's device count; equal-size groups share
        one compiled program, so the trace bound stays one per (bucket,
        group size).

        Returns an :class:`InFlightWave`; :meth:`finish_wave` blocks on it
        and yields the results.  The split is what the continuous
        scheduler's dispatch lanes pull on: a lane can launch its wave
        while earlier waves still execute, overlapping host padding with
        device compute.
        """
        if not 0 < len(wave) <= self.slots:
            raise ValueError(
                f"wave of {len(wave)} requests (engine slots={self.slots})")
        index = self.waves
        with spans.span("serve.launch", wave=index, bucket=bucket):
            mesh = self.mesh if submesh is None else submesh
            lanes = 1 if mesh is None else int(mesh.devices.size)
            if submesh is not None and self.slots % lanes:
                raise ValueError(
                    f"slots={self.slots} not divisible by the {lanes}-device "
                    f"submesh group")
            cm = self._compile(bucket)
            slot_of = self._slot_layout(wave, lanes)
            # ONE zero-initialized (slots, ...) buffer per graph input,
            # filled slot-by-slot in place: dummy slots stay all-zero
            # (all-SKIP plans) with no per-slot dict or np.stack copy, and
            # store-backed requests gather their feature rows straight
            # into their slot (``_fill_slot``'s fill_features hook).
            with spans.span("serve.fill", wave=index):
                batched = {name: np.zeros(
                    (self.slots,) + self._input_shape(name, bucket),
                    np.float32) for name in self._input_names[bucket]}
                for req, slot in zip(wave, slot_of):
                    self._fill_slot(req, {name: buf[slot]
                                          for name, buf in batched.items()})
            # sharded waves stay host-side here: launch_batch device_puts
            # them straight onto the mesh (one host->per-device-shard
            # transfer); staging through jnp.asarray first would land the
            # full stack on one device and reshard from there.
            if mesh is None:
                batched = {name: jnp.asarray(v)
                           for name, v in batched.items()}
            pending = self.executor.launch_batch(cm, self.weights, batched,
                                                 mesh=mesh)
        self.waves += 1
        return InFlightWave(bucket=bucket, wave=list(wave), slot_of=slot_of,
                            pending=pending,
                            final=cm.graph.kernels[-1].out, index=index)

    def finish_wave(self, inflight: "InFlightWave") -> List[GraphResult]:
        """Block on a :meth:`begin_wave` launch, record the serving
        counters (``served``/``wave_walls``/``wave_loads``/
        ``bucket_walls``), stamp the wave report
        (``last_wave_report.wave_real``), and slice per-request results
        back out (wave order)."""
        outs, rep = self.executor.finish_batch(inflight.pending)
        rep.wave_real = len(inflight.wave)
        self.last_wave_report = rep
        arr = np.asarray(outs[inflight.final])
        results = [GraphResult(req.request_id, arr[slot, : req.n_vertices],
                               inflight.bucket, inflight.index)
                   for slot, req in zip(inflight.slot_of, inflight.wave)]
        self.served += len(inflight.wave)
        self.wave_walls.append(rep.fused_wall_seconds)
        self.wave_loads.append((len(inflight.wave), self.slots))
        self.bucket_walls.setdefault(inflight.bucket, []).append(
            rep.fused_wall_seconds)
        self.group_walls.setdefault(inflight.pending.lanes, []).append(
            rep.fused_wall_seconds)
        return results

    def dispatch_wave(self, bucket: int, wave: Sequence[GraphRequest]
                      ) -> List[GraphResult]:
        """Execute one admission wave: pad each request to ``bucket``, fill
        the remaining slots with zero dummies, run ONE batched fused
        dispatch, and slice per-request results back out (wave order).

        This is the reusable backend step behind both :meth:`serve` and the
        continuous scheduler (``serving.scheduler.ContinuousGraphServer``);
        it owns the serving counters (``waves``/``served``/``wave_walls``/
        ``bucket_walls``/``wave_loads``) and stamps the wave's real-slot
        count into the report (``last_wave_report.wave_real``).  With a
        ``cores`` mesh the dispatch is device-sharded: requests are placed
        into per-device slot ranges by cost-aware LPT bins
        (:meth:`_slot_layout`) and ``run_batch`` scans each device's range
        on its own device.  :meth:`begin_wave`/:meth:`finish_wave` are the
        non-blocking halves (the continuous scheduler's lanes use them to
        keep several waves in flight).
        """
        return self.finish_wave(self.begin_wave(bucket, wave))

    def serve(self, requests: Sequence[GraphRequest]) -> List[GraphResult]:
        """Serve a batch of queries; results in request order."""
        results: List[Optional[GraphResult]] = [None] * len(requests)
        for bucket, waves in self._admit(requests).items():
            for wave in waves:
                wave_results = self.dispatch_wave(
                    bucket, [req for _, req in wave])
                for (idx, _), res in zip(wave, wave_results):
                    results[idx] = res
        return results  # type: ignore[return-value]

    def run_naive(self, requests: Sequence[GraphRequest]
                  ) -> List[GraphResult]:
        """Per-request baseline AND bitwise parity oracle: the same
        pad-to-bucket admission, but one single-inference
        ``FusedModelExecutor.run`` per request -- no wave batching, a
        different program from the wave's scan over stacked slots.  The
        serving benchmark compares throughput against this; the tests
        compare bits."""
        results = []
        for req in requests:
            self._validate(req)
            bucket = self.bucket_for(req.n_vertices)
            cm = self._compile(bucket)
            tensors = dict(self.weights)
            tensors.update({name: jnp.asarray(v)
                            for name, v in self._padded(req, bucket).items()})
            env, _ = self._naive.run(cm, tensors)
            final = cm.graph.kernels[-1].out
            results.append(GraphResult(
                req.request_id,
                np.asarray(env[final])[: req.n_vertices], bucket, -1))
        return results
