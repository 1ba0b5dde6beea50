"""Continuous deadline-aware GNN serving: queue -> cut -> pack -> stream.

The batched :class:`~repro.serving.graph_engine.GraphServeEngine` admits a
*synchronous* batch: every request is present up front, waves are cut per
bucket, results come back when the whole batch is done.  A deployed GNN
service sees none of that -- queries ARRIVE over time (the paper's runtime
profiles each arriving graph and re-plans per input; Algorithm 8's task
queue is fed continuously), carry latency expectations, and want their
result the moment their wave completes.  :class:`ContinuousGraphServer` is
that online layer (DESIGN.md section 11):

* **Time-ordered queue.**  :meth:`submit` validates a request, assigns it
  to its shape bucket, and appends it (with its arrival time and optional
  absolute deadline) to the bucket's FIFO.  Nothing executes at submit
  time; :meth:`poll` is the scheduler tick.

* **Deadline-aware wave cutting.**  A bucket's queue is cut the moment a
  full wave of ``slots`` requests is available (reason ``"full"``).  A
  *partial* wave is cut early when some queued request can no longer
  afford to wait: the TIGHTEST queued deadline's slack
  (``deadline - now``; a forced cut takes the whole sub-slots queue, so
  FIFO position must not starve a tight deadline behind a loose one) has
  dropped to within the bucket's estimated WAIT BOUND (reason
  ``"deadline"``), or the oldest request has waited ``max_wait``
  regardless of deadline (reason ``"age"`` -- the starvation-freedom
  backstop for deadline-less traffic).  The wait bound
  is the LPT makespan, over the ``n_lanes`` dispatch lanes, of the
  bucket's estimated wave wall plus one estimated wave from every
  other bucket with queued work (those buckets' waves may cut in the same
  tick, and busy lanes delay this one; with one lane this is the serial
  sum), scaled by
  ``slack_margin``; per-bucket wave-wall estimates are an EWMA over
  observed dispatch walls, cold-started from the engine's recorded
  ``bucket_walls``/``wave_walls`` (or ``cold_start_wall`` when the bucket
  has never run).  The age cut fires after
  ``min(max_wait, batch_patience * estimate)``: waiting longer than a
  wave costs to run cannot be amortized by a fuller wave, so batching
  patience adapts to the bucket's measured wall instead of idling on a
  fixed timer.

* **Cross-bucket packing.**  All waves cut in one tick are ordered by
  ``core.scheduler.schedule_lpt`` over their estimated walls -- the
  Analyzer-predicted-cost LPT policy the engine already uses for task
  bins, applied at wave granularity -- with deadline/age-triggered waves
  promoted ahead of full ones.  Every cut wave dispatches within the same
  tick, so large buckets can never starve small ones (or vice versa); LPT
  just fixes a deterministic, longest-first launch order.

* **Slot-level result streaming.**  Results surface per request as each
  wave completes: :meth:`poll` returns the newly finished
  :class:`~repro.serving.graph_engine.GraphResult` objects (stamped with
  ``completed_at`` and their ``deadline``), not a batch-final list.
  :meth:`drain` force-cuts everything left and flushes the stream.

* **Overload control** (DESIGN.md section 15).  The server no longer
  admits every request and chases every deadline.  :meth:`submit` returns
  a structured :class:`Ticket` carrying an admission verdict (``admit`` /
  ``admit-at-risk`` / ``shed``) classified from a predicted-completion
  estimate (per-request Analyzer cost through a measured
  seconds-per-cost-unit calibration, packed against the queue backlog
  over the EWMA walls); the ``shed=`` policy decides whether a predicted
  miss is rejected at the door.  Requests carry ``priority``/``tenant``
  classes: full waves are composed highest-class-first (with an age-based
  starvation backstop), cut waves dispatch in class-weighted LPT order
  (``core.scheduler.schedule_weighted``), and per-class counters
  (``class_stats``: admitted/shed/met/missed) plus a backlog pressure
  gauge stream to the observability surface.  When the backlog's
  heterogeneous-LPT bound exceeds ``pressure_threshold``, the scheduler
  degrades by policy: lowest-class at-risk queued requests are shed
  first, and (resize mode) ``autoscale=True`` re-picks the
  ``plan_groups`` lane count each tick from the per-size EWMA walls
  (:func:`plan_lanes`).  None of this touches numerics: admitted work
  stays bitwise-identical to ``run_naive`` whatever the priorities,
  tenants, or arrival order.

The clock is injectable (``clock=``, default ``time.monotonic``) so the
whole policy runs deterministically under a fake clock in tests
(``tests/test_continuous_serving.py``); numerics never depend on it --
continuous results are bitwise-identical to
``GraphServeEngine.run_naive`` (each request served alone by the
single-inference program) on the same requests whatever the arrival
order, deadlines, or clock jitter.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import spans
from repro.core import perf_model
from repro.core import scheduler as core_scheduler
from repro.distributed import sharding as dist_sharding
from repro.serving.config import UNSET, ServeConfig, merge_config
from repro.serving.graph_engine import (GraphRequest, GraphResult,
                                        GraphServeEngine, InFlightWave)


def plan_groups(n_devices: int, demands: Sequence[float], slots: int,
                max_groups: Optional[int] = None) -> List[int]:
    """Plan disjoint device-group sizes for one dispatch tick.

    Pure resize policy (property-tested in
    ``tests/test_submesh_partition.py``): given ``n_devices`` mesh devices,
    the estimated walls of the waves wanting to run (``demands``), and the
    engine's wave ``slots``, return group sizes for
    ``distributed.sharding.partition_mesh`` -- every size positive,
    dividing ``slots`` (the engine splits a wave's slots evenly over its
    group), summing EXACTLY to ``n_devices``.

    The first ``k = min(len(demands), n_devices, max_groups)`` entries are
    the demand-assigned groups, aligned with ``demands`` sorted descending
    (largest demand <-> widest group); trailing ``1``s are spare devices
    kept idle this tick.  Groups start at one device each and the group
    with the highest remaining demand/size ratio greedily doubles while
    spare devices allow, so a lone huge wave grabs the whole mesh while
    many small waves pack one device each (DESIGN.md section 14).
    """
    if n_devices < 1:
        raise ValueError(f"plan_groups over {n_devices} devices")
    if slots < 1:
        raise ValueError(f"plan_groups with {slots} wave slots")
    dem = [float(x) for x in demands]
    if not dem:
        raise ValueError("plan_groups with no demands")
    if any(x < 0 for x in dem):
        raise ValueError(f"negative demand in {demands}")
    k = min(len(dem), n_devices)
    if max_groups is not None:
        if max_groups < 1:
            raise ValueError(f"max_groups {max_groups} < 1")
        k = min(k, max_groups)
    dem = sorted(dem, reverse=True)[:k]
    sizes = [1] * k
    spare = n_devices - k
    while spare > 0:
        best, best_ratio = -1, -1.0
        for i in range(k):
            doubled = sizes[i] * 2
            if sizes[i] > spare:           # doubling adds sizes[i] devices
                continue
            if doubled > slots or slots % doubled:
                continue                   # group must divide the slots
            ratio = dem[i] / sizes[i]
            if ratio > best_ratio:
                best, best_ratio = i, ratio
        if best < 0:
            break
        spare -= sizes[best]
        sizes[best] *= 2
    # greedy-by-ratio keeps sizes descending alongside the sorted demands
    # (equal sizes tie-break toward the larger demand), so the pairing
    # "i-th largest demand <-> i-th entry" holds without re-sorting
    return sizes + [1] * spare


def plan_lanes(n_devices: int, demands: Sequence[float], slots: int,
               max_lanes: int,
               size_wall: Optional[Callable[[int], float]] = None) -> int:
    """Pick the lane count whose :func:`plan_groups` split finishes first.

    Pure autoscale policy (resize mode, ``autoscale=True``): for each
    candidate lane count ``k`` up to ``max_lanes``, plan the device-group
    sizes and pack the ``demands`` (estimated wave walls, any order)
    longest-first over the ``k`` groups -- each wave costed at no less
    than its group's per-size wall from ``size_wall`` (the scheduler
    passes its per-size EWMA estimates; ``None`` skips the floor) -- and
    return the ``k`` with the smallest predicted finish.  Ties prefer
    MORE lanes (parallel headroom costs nothing when the bound agrees),
    so a backlog of many small waves spreads wide while a lone huge wave
    collapses the plan to one full-mesh group whose measured wall is
    genuinely lower (DESIGN.md section 15).
    """
    if max_lanes < 1:
        raise ValueError(f"max_lanes {max_lanes} < 1")
    dem = sorted((float(x) for x in demands), reverse=True)
    if not dem:
        raise ValueError("plan_lanes with no demands")
    best_k, best_t = 1, math.inf
    for k in range(1, min(len(dem), n_devices, max_lanes) + 1):
        sizes = plan_groups(n_devices, dem, slots, max_groups=k)
        finish = [0.0] * k
        for c in dem:
            g = min(range(k), key=lambda j: (finish[j], j))
            floor = size_wall(sizes[g]) if size_wall is not None else 0.0
            finish[g] += max(c, floor)
        t = max(finish)
        if t <= best_t + 1e-12:
            best_k, best_t = k, min(t, best_t)
    return best_k


class Ticket(int):
    """Structured admission ticket returned by
    :meth:`ContinuousGraphServer.submit`.

    An ``int`` subclass whose integer value IS the submission sequence
    number, so every pre-overload caller keeps working unchanged --
    ``int(ticket)``, equality/hashing against plain ints, dict keys,
    format args all behave exactly like the old bare-int return.  On top
    of that it carries the admission decision:

    * ``verdict`` -- ``"admit"`` | ``"admit-at-risk"`` | ``"shed"``
      (``admitted`` is the convenience bool; a shed ticket's request was
      REJECTED and will never produce a result);
    * ``predicted_miss`` -- the raw signal: completion was predicted past
      the deadline at submit time, whatever the shed policy did about it;
    * ``predicted_wall`` -- the predicted seconds until this request's
      result (queue backlog pack + calibrated own-wave wall);
    * ``bucket``, ``priority``, ``tenant``, ``deadline`` -- the
      admission-time classification, echoed back.

    The verdict bands, in classification order (``slack`` is
    ``deadline - now``, infinite for best-effort requests; ``W`` is
    ``predicted_wall``; ``m`` is the server's ``admit_margin >= 1``):

    ===================================  ===============================
    band                                 verdict
    ===================================  ===============================
    queue full (``shed="capacity"``      ``"shed"`` (before any
    and ``pending >= max_pending``)      prediction is consulted)
    ``slack < W`` (a predicted miss)     ``"shed"`` under
                                         ``shed="predicted-miss"``,
                                         else ``"admit-at-risk"``
    ``W <= slack < m * W``               ``"admit-at-risk"`` -- admitted,
                                         but with less than the margin's
                                         headroom; first to go if
                                         backlog pressure degrades
    ``slack >= m * W``                   ``"admit"``
    ===================================  ===============================
    """

    def __new__(cls, seq: int, *, bucket: int = 0,
                predicted_wall: float = 0.0, verdict: str = "admit",
                predicted_miss: bool = False, priority: int = 0,
                tenant: str = "default",
                deadline: Optional[float] = None):
        self = super().__new__(cls, seq)
        self.bucket = int(bucket)
        self.predicted_wall = float(predicted_wall)
        self.verdict = str(verdict)
        self.predicted_miss = bool(predicted_miss)
        self.priority = int(priority)
        self.tenant = str(tenant)
        self.deadline = deadline
        return self

    @property
    def seq(self) -> int:
        return int(self)

    @property
    def admitted(self) -> bool:
        return self.verdict != "shed"

    def __repr__(self) -> str:
        return (f"Ticket({int(self)}, bucket={self.bucket}, "
                f"verdict={self.verdict!r}, "
                f"predicted_wall={self.predicted_wall:.4g}, "
                f"predicted_miss={self.predicted_miss}, "
                f"priority={self.priority}, tenant={self.tenant!r})")

    # printing/formatting a ticket must keep producing the bare number
    # (callers log ticket ids with f-strings); only repr is structured
    __str__ = int.__repr__


@dataclasses.dataclass
class ClassStats:
    """Per-(tenant, priority) serving counters (DESIGN.md section 15).

    ``admitted`` counts requests enqueued at submit; ``shed`` counts
    rejections at the admission door PLUS pressure sheds pulled back out
    of the queue; ``met``/``missed`` split delivered results by deadline
    outcome (deadline-less deliveries count as ``met`` -- they cannot
    miss).  Conservation: submits == admitted + door sheds, and
    admitted == delivered + pressure sheds + still-queued.
    """

    admitted: int = 0
    shed: int = 0
    met: int = 0
    missed: int = 0

    @property
    def delivered(self) -> int:
        return self.met + self.missed


@dataclasses.dataclass
class QueuedRequest:
    """One queue entry: the request plus its admission-time metadata."""

    seq: int                        # submission order (ticket id)
    request: GraphRequest
    bucket: int
    arrival: float                  # clock time at submit
    deadline: Optional[float]       # ABSOLUTE clock deadline (None = none)
    priority: int = 0               # class: higher dispatches sooner
    tenant: str = "default"         # accounting stream for class_stats
    cost: float = 0.0               # Analyzer cost units (calibration)
    ticket: Optional[Ticket] = None


@dataclasses.dataclass
class WaveLog:
    """Dispatch-log entry: one cut wave, why it was cut, what it cost."""

    bucket: int
    n_real: int                     # real (non-dummy) requests in the wave
    reason: str                     # "full" | "deadline" | "age" | "drain"
    cut_at: float                   # clock time the cut decision was made
    wall: float                     # dispatch wall seconds (engine-measured)
    lane: int = 0                   # dispatch lane the wave was pulled by
    group_size: int = 1             # device-group width the wave ran on
    #                                 (resize mode; 1-lane/unsharded = 1)
    classes: Dict[int, int] = dataclasses.field(default_factory=dict)
    #                                 priority -> real-request count (the
    #                                 wave's class composition)


class _EwmaWall:
    """Per-bucket EWMA wave-wall estimate with explicit cold start.

    ``observe`` folds each measured dispatch wall in with weight ``alpha``;
    before the first observation the estimate comes from the seed (the
    MINIMUM of the engine's recorded walls: dispatch walls are bounded
    below by the true compute and their outliers -- the first wave's
    trace, host scheduling noise -- are always upward, so min is the
    steady-state proxy) or ``cold_start`` when the bucket never ran.
    """

    def __init__(self, alpha: float, seed: Optional[float],
                 cold_start: float):
        self.alpha = alpha
        self.value = cold_start if seed is None else float(seed)

    def observe(self, wall: float) -> None:
        self.value += self.alpha * (float(wall) - self.value)


class ContinuousGraphServer:
    """Deadline-aware online scheduler over a :class:`GraphServeEngine`.

    >>> eng = GraphServeEngine("gcn", f_in=64, n_classes=7, slots=4)
    >>> srv = ContinuousGraphServer(eng)        # or config=ServeConfig(...)
    >>> t = srv.submit(req, deadline=srv.clock() + 0.05, priority=1)
    >>> int(t), t.verdict, t.predicted_miss    # Ticket is an int subclass
    (0, 'admit', False)
    >>> done = srv.poll()          # dispatches any cuttable waves
    >>> tail = srv.drain()         # force-flush at shutdown

    Contracts:

    * every submitted request is dispatched in exactly one wave of at most
      ``engine.slots`` requests, eventually (starvation-freedom: full cut,
      deadline cut, ``max_wait`` age cut, or :meth:`drain`);
    * results are bitwise-identical to ``engine.run_naive`` on the same
      requests -- arrival order, deadlines, and clock behavior select wave
      composition, never numerics -- and ``engine.executor.trace_count``
      still grows by at most one per shape bucket (per (bucket, group
      size) under ``resize=True``: equal-size groups share one program);
    * within one :meth:`poll` tick, cut waves dispatch in LPT order over
      the per-bucket EWMA wall estimates (urgent deadline/age cuts first),
      each pulled by the earliest-idle of the ``n_lanes`` dispatch lanes
      (one lane per device group; defaults to the engine's cores-mesh
      device count, 1 when unsharded) -- the deadline-slack wait bound is
      the LPT makespan over the lanes, not the serial sum;
    * ``dispatch_log`` records every wave (bucket, real slots, cut reason,
      measured wall, pulling lane) for tests and observability.

    ``slack_margin`` scales the wait bound in the slack comparison (>1
    cuts earlier; the default 1.5 buys headroom against wall variance and
    the host-side padding cost the device wall doesn't see).

    ``resize=True`` (requires an engine mesh) switches the lanes from
    slot-ranges of one shared mesh to DISJOINT device groups, replanned
    between waves from queue composition by :func:`plan_groups`: a huge
    wave grabs a wide group while small waves pack one device each, each
    wave dispatching via ``begin_wave(submesh=...)`` on its group's
    devices only (DESIGN.md section 14).  EWMA walls are additionally
    tracked per group SIZE (:meth:`group_estimate`), the deadline-slack
    wait bound becomes the heterogeneous-capacity LPT makespan over the
    planned groups, and ``n_lanes=1`` always plans the single full-mesh
    group -- shared-mesh single-lane semantics, exactly.
    """

    def __init__(self, engine: GraphServeEngine, *,
                 config: Optional[ServeConfig] = None,
                 clock: Callable[[], float] = UNSET,
                 ewma_alpha: float = UNSET,
                 cold_start_wall: float = UNSET,
                 slack_margin: float = UNSET,
                 batch_patience: float = UNSET,
                 max_wait: float = UNSET,
                 n_lanes: Optional[int] = UNSET,
                 resize: bool = UNSET,
                 shed: str = UNSET,
                 admit_margin: float = UNSET,
                 max_pending: Optional[int] = UNSET,
                 pressure_threshold: float = UNSET,
                 priority_weight: float = UNSET,
                 autoscale: bool = UNSET,
                 minibatch=UNSET):
        cfg = merge_config(ServeConfig, config, dict(
            clock=clock, ewma_alpha=ewma_alpha,
            cold_start_wall=cold_start_wall, slack_margin=slack_margin,
            batch_patience=batch_patience, max_wait=max_wait,
            n_lanes=n_lanes, resize=resize, shed=shed,
            admit_margin=admit_margin, max_pending=max_pending,
            pressure_threshold=pressure_threshold,
            priority_weight=priority_weight,
            autoscale=autoscale, minibatch=minibatch)).validate()
        if cfg.resize and engine.mesh is None:
            raise ValueError(
                "resize=True needs an engine with a cores mesh to partition")
        self.config = cfg
        self.engine = engine
        self.clock = cfg.clock
        self.ewma_alpha = cfg.ewma_alpha
        self.cold_start_wall = cfg.cold_start_wall
        self.slack_margin = cfg.slack_margin
        self.batch_patience = cfg.batch_patience
        self.max_wait = cfg.max_wait
        # overload-control policy (DESIGN.md section 15)
        self.shed = cfg.shed
        self.admit_margin = cfg.admit_margin
        self.max_pending = cfg.max_pending
        self.pressure_threshold = cfg.pressure_threshold
        self.priority_weight = cfg.priority_weight
        self._autoscale = bool(cfg.autoscale)
        # dispatch lanes: one per device group (default: one per device of
        # the engine's cores mesh; 1 when unsharded).  Waves cut in one
        # tick are pulled by the earliest-idle lane, so the wait a queued
        # request sees is the LPT makespan over the lanes, not the serial
        # sum -- ``wait_bound`` models exactly that.
        n_lanes = engine.lanes if cfg.n_lanes is None else int(cfg.n_lanes)
        self.n_lanes = n_lanes
        # rebind the sentinel-defaulted locals the rest of the constructor
        # reads to their RESOLVED values
        resize = cfg.resize
        ewma_alpha = cfg.ewma_alpha
        cold_start_wall = cfg.cold_start_wall
        # resize mode: between waves, partition the engine's mesh into
        # DISJOINT per-lane device groups sized from queue composition
        # (``plan_groups``) and dispatch each wave on its own group via
        # ``begin_wave(submesh=...)`` -- lanes stop contending on one
        # shared device set (DESIGN.md section 14).  ``n_lanes`` caps the
        # concurrent group count; with ``n_lanes=1`` the plan is always
        # the single full-mesh group, reproducing the shared-mesh
        # single-lane semantics exactly.
        self._resize = bool(resize)
        self.n_devices = engine.lanes
        # per-group-SIZE EWMA walls (the heterogeneous-capacity floor in
        # ``wait_bound``); seeded from the engine's recorded group_walls.
        self._group_ewma: Dict[int, _EwmaWall] = {}
        self.last_group_sizes: List[int] = []
        self._queues: Dict[int, List[QueuedRequest]] = {}
        self._ewma: Dict[int, _EwmaWall] = {}
        # per-lane EWMA of the wave walls that lane pulled (observability +
        # the lane-balance tests); cold-started like a never-run bucket.
        # The cold start deliberately stays pessimistic: never-pulled
        # lanes keep the shared-mesh wait bound high, cutting waves small
        # and early -- which measures FASTER than fuller waves on the
        # shared device set (overlapped full-mesh programs contend; see
        # the recorded multidevice_rows).  Resize mode never reads these:
        # its bound floors on the per-SIZE group walls instead, which are
        # seeded from measured steady-state dispatches.
        self._lane_ewma: List[_EwmaWall] = [
            _EwmaWall(ewma_alpha, None, cold_start_wall)
            for _ in range(n_lanes)]
        # round-robin tie-break for idle-lane selection: ticks that cut a
        # single wave would otherwise always pick lane 0, leaving the
        # other lanes' EWMA walls frozen at cold start.
        self._next_lane = 0
        # results harvested during a tick that then failed mid-dispatch:
        # the next poll()/drain() delivers them (results must never be
        # dropped once their wave completed).
        self._undelivered: List[GraphResult] = []
        self._seq = 0
        self.dispatch_log: List[WaveLog] = []
        self.submitted = 0
        self.dispatched = 0
        # overload-control observability: per-(tenant, priority) counters,
        # the tickets of every shed request (door + pressure), the raw
        # shed split, and the highest backlog bound any tick has seen.
        self.class_stats: Dict[Tuple[str, int], ClassStats] = {}
        self.shed_log: List[Ticket] = []
        self.admitted = 0
        self.shed_at_submit = 0
        self.shed_under_pressure = 0
        self.peak_pressure = 0.0
        self.last_auto_lanes: Optional[int] = None
        # giant-graph mini-batch front door (DESIGN.md section 16): a
        # serving.minibatch.MiniBatchPlanner samples one subgraph per
        # seed vertex, answers hot seeds from its vertex cache, and maps
        # planner-issued (negative) request ids back to waiting queries.
        # Whole-graph submit() callers should keep request ids
        # non-negative so routing never mistakes their results.
        self.minibatch = cfg.minibatch
        self._query_seq = 0
        self.queries_submitted = 0
        self._query_waiters: Dict[int, List] = {}   # request_id -> tickets
        self._inflight_seed: Dict[int, int] = {}    # vertex -> request_id
        # seconds-per-cost-unit calibration: Analyzer cost units of each
        # dispatched wave against its measured wall, so admission can
        # floor a request's own-wave estimate by its PREDICTED cost even
        # when its bucket's EWMA is still cold
        self._calib = perf_model.CostCalibration(alpha=cfg.ewma_alpha)
        # wave-occupancy feedback for the admission/backlog model: under
        # deadline pressure waves cut PARTIAL, so clearing q requests
        # costs ceil(q / measured-real-per-wave) walls, not ceil(q /
        # slots).  EWMA of each dispatched wave's real count, seeded at
        # full occupancy (= the optimistic pre-overload assumption).
        self._occupancy = _EwmaWall(cfg.ewma_alpha, float(engine.slots),
                                    float(engine.slots))
        # server-level wall-clock per wave (cut -> delivery), an EWMA
        # floor for the admission/backlog model only: bucket EWMAs
        # measure the DEVICE wall (launch -> ready), but each wave also
        # pays host prep/teardown, and admission that ignores it admits
        # requests doomed to miss.  Cold start 0.0 = no floor, so cut
        # policy and clock-frozen tests see the pre-overload model.
        self._wave_floor = _EwmaWall(cfg.ewma_alpha, None, 0.0)
        # self-calibrating admission: EWMA of (actual sojourn / the
        # sojourn the ticket itself predicted), observed at every
        # delivery.  The pack model cannot see tick granularity, fill
        # wait, or priority reordering; whatever it systematically misses
        # shows up here and scales future admission bounds.  Only ratios
        # > 1 are applied (max(1, bias) at the door): an optimistic model
        # sheds too little and must be corrected, a pessimistic one
        # already errs safe -- and clock-frozen tests (sojourn 0) keep
        # their pinned verdicts.
        self._model_bias = _EwmaWall(cfg.ewma_alpha, 1.0, 1.0)

    @classmethod
    def from_config(cls, engine: GraphServeEngine,
                    config: ServeConfig) -> "ContinuousGraphServer":
        """Round-trip constructor:
        ``ContinuousGraphServer.from_config(srv.engine, srv.config)``
        builds a server with the exact same policy."""
        return cls(engine, config=config)

    # -- queue --------------------------------------------------------------
    def submit(self, request: GraphRequest,
               deadline: Optional[float] = None, *,
               priority: int = 0, tenant: str = "default") -> Ticket:
        """Enqueue one request; returns its admission :class:`Ticket`.

        The ticket is an ``int`` (the submission sequence, exactly the old
        return value) carrying the admission decision: a predicted
        completion (:meth:`admission_estimate`: queue backlog packed over
        the EWMA walls, the request's own wave floored by its calibrated
        Analyzer cost) classifies the request ``admit`` /
        ``admit-at-risk`` / ``shed`` against its deadline slack, and the
        ``shed=`` policy decides whether a predicted miss (or, under
        ``shed="capacity"``, a full queue) is rejected at the door.  A
        shed ticket's request is NOT queued and never produces a result
        (check ``ticket.admitted``).

        ``deadline`` is an ABSOLUTE time on this server's clock (pass
        ``srv.clock() + budget``); ``None`` means best-effort -- the
        request still dispatches within ``max_wait`` of arrival and is
        never shed by deadline prediction.  ``priority`` (higher = more
        urgent, default 0) and ``tenant`` set the request's class for
        weighted-fair dispatch and per-class accounting; neither ever
        changes numerics, only ordering.  The request is validated here
        (malformed input must fail at the admission edge, not poison a
        wave later).
        """
        self.engine._validate(request)
        bucket = self.engine.bucket_for(request.n_vertices)
        now = self.clock()
        cost = float(self.engine.request_cost(request))
        # measured-bias correction: scale the pack model's estimate by how
        # much actual sojourns have been exceeding predicted ones (never
        # below 1x -- see _model_bias)
        bound = (self.admission_estimate(bucket, cost)
                 * max(1.0, self._model_bias.value))
        slack = math.inf if deadline is None else deadline - now
        predicted_miss = slack < bound
        if (self.shed == "capacity" and self.max_pending is not None
                and self.pending >= self.max_pending):
            verdict = "shed"
        elif predicted_miss:
            verdict = ("shed" if self.shed == "predicted-miss"
                       else "admit-at-risk")
        elif slack < self.admit_margin * bound:
            verdict = "admit-at-risk"
        else:
            verdict = "admit"
        seq = self._seq
        self._seq += 1
        self.submitted += 1
        ticket = Ticket(seq, bucket=bucket, predicted_wall=bound,
                        verdict=verdict, predicted_miss=predicted_miss,
                        priority=int(priority), tenant=str(tenant),
                        deadline=deadline)
        stats = self._stats_for(ticket.tenant, ticket.priority)
        if verdict == "shed":
            stats.shed += 1
            self.shed_at_submit += 1
            self.shed_log.append(ticket)
            return ticket
        stats.admitted += 1
        self.admitted += 1
        self._queues.setdefault(bucket, []).append(QueuedRequest(
            seq, request, bucket, now, deadline, priority=ticket.priority,
            tenant=ticket.tenant, cost=cost, ticket=ticket))
        return ticket

    def submit_query(self, seeds: Sequence[int],
                     deadline: Optional[float] = None, *,
                     priority: int = 0, tenant: str = "default"):
        """Giant-graph front door (DESIGN.md section 16): enqueue one
        mini-batch QUERY -- seed vertices of the planner's host graph --
        alongside whole-graph :meth:`submit` traffic.

        Per (unique) seed vertex: a hot-vertex cache hit answers
        immediately; a vertex already in flight coalesces (one sampled
        request serves every query waiting on it -- exact, because each
        vertex's subgraph is sampled under its own derived seed, so the
        result is query-independent); otherwise the planner samples the
        vertex's subgraph and the request is submitted through the normal
        admission door (deadline/priority/tenant apply per seed request;
        a shed seed is recorded on ``ticket.shed_seeds`` and its row
        stays NaN).  Coalescing is version-checked on BOTH axes of
        mutation: an in-flight request that gathered features before a
        store update, or that was sampled before an edge delta bumped
        the planner's ``graph_version``, is NOT joined by a query
        submitted after it -- the new query gets a fresh post-update
        request, so no result ever reflects features or topology older
        than its own submission.

        Returns a :class:`~repro.serving.minibatch.QueryTicket`; rows
        fill as :meth:`poll`/:meth:`drain` complete waves (check
        ``ticket.done``, then ``ticket.result()``).  Requires a
        ``minibatch=`` planner (``ServeConfig.minibatch``).
        """
        from repro.serving.minibatch import QueryTicket
        planner = self.minibatch
        if planner is None:
            raise ValueError(
                "submit_query needs a minibatch planner: "
                "ContinuousGraphServer(engine, "
                "minibatch=MiniBatchPlanner(graph, store, ...))")
        with spans.span("serve.submit", query=self._query_seq):
            qt = QueryTicket(self._query_seq, [int(v) for v in seeds],
                             deadline=deadline)
            self._query_seq += 1
            self.queries_submitted += 1
            for v in dict.fromkeys(qt.seeds):
                row = planner.lookup(v)
                if row is not None:
                    qt.from_cache += 1
                    qt._fill(v, row)
                    continue
                qt._pending.add(v)
                rid = self._inflight_seed.get(v)
                if rid is not None and rid in self._query_waiters:
                    inflight = planner.inflight_request(rid)
                    if (inflight is not None and inflight.store_version
                            == planner.store.version
                            and inflight.graph_version
                            == planner.graph_version):
                        self._query_waiters[rid].append(qt)
                        continue
                req = planner.request_for(v)
                ticket = self.submit(req, deadline, priority=priority,
                                     tenant=tenant)
                qt.tickets.append(ticket)
                if not ticket.admitted:
                    planner.abandon(req)
                    qt.shed_seeds.append(v)
                    qt._fill(v, None)
                    continue
                self._query_waiters[req.request_id] = [qt]
                self._inflight_seed[v] = req.request_id
        return qt

    def apply_delta(self, edge_inserts: Sequence = (),
                    edge_deletes: Sequence = ()):
        """Stream an edge delta into the served giant graph (DESIGN.md
        §17): delegates to
        :meth:`~repro.serving.minibatch.MiniBatchPlanner.apply_delta`
        and returns its :class:`~repro.serving.minibatch.DeltaReport`.

        Safe mid-stream: requests already in flight were sampled from
        the old topology and still deliver (their snapshot is
        consistent), but their rows are never cached and later queries
        never coalesce onto them -- ``submit_query`` re-checks the
        planner's ``graph_version`` at the coalescing point.
        """
        if self.minibatch is None:
            raise ValueError(
                "apply_delta needs a minibatch planner: "
                "ContinuousGraphServer(engine, "
                "minibatch=MiniBatchPlanner(graph, store, ...))")
        return self.minibatch.apply_delta(edge_inserts, edge_deletes)

    def _route(self, results: List[GraphResult]) -> List[GraphResult]:
        """Split a tick's delivered results: planner-issued seed requests
        route to their waiting query tickets (filling the vertex cache
        via ``planner.complete``); everything else streams back to the
        whole-graph caller unchanged."""
        if self.minibatch is None or not self._query_waiters:
            return results
        out = []
        for res in results:
            waiters = self._query_waiters.pop(res.request_id, None)
            if waiters is None:
                out.append(res)
                continue
            vertex, row = self.minibatch.complete(res)
            if self._inflight_seed.get(vertex) == res.request_id:
                del self._inflight_seed[vertex]
            for qt in waiters:
                qt._fill(vertex, row, completed_at=res.completed_at)
        return out

    def _stats_for(self, tenant: str, priority: int) -> ClassStats:
        key = (tenant, priority)
        stats = self.class_stats.get(key)
        if stats is None:
            stats = self.class_stats[key] = ClassStats()
        return stats

    def _account_delivery(self, entry: QueuedRequest, done_at: float) -> None:
        stats = self._stats_for(entry.tenant, entry.priority)
        if entry.deadline is None or done_at <= entry.deadline:
            stats.met += 1
        else:
            stats.missed += 1
        # close the admission feedback loop: actual sojourn vs the sojourn
        # this very ticket predicted at the door (clamped: one outlier
        # must not swing the EWMA by orders of magnitude)
        if entry.ticket is not None and entry.ticket.predicted_wall > 1e-9:
            ratio = (done_at - entry.arrival) / entry.ticket.predicted_wall
            self._model_bias.observe(min(8.0, max(0.25, ratio)))

    @staticmethod
    def _wave_classes(wave: List[QueuedRequest]) -> Dict[int, int]:
        classes: Dict[int, int] = {}
        for e in wave:
            classes[e.priority] = classes.get(e.priority, 0) + 1
        return classes

    @property
    def pending(self) -> int:
        """Requests queued but not yet dispatched."""
        return sum(len(q) for q in self._queues.values())

    @property
    def pressure(self) -> float:
        """Current backlog pressure gauge: :meth:`backlog_bound` seconds."""
        return self.backlog_bound()

    def estimate(self, bucket: int) -> float:
        """Current EWMA wave-wall estimate for ``bucket`` (seconds)."""
        return self._ewma_for(bucket).value

    def _ewma_for(self, bucket: int) -> _EwmaWall:
        est = self._ewma.get(bucket)
        if est is None:
            own = self.engine.bucket_walls.get(bucket)
            if own:
                seed = float(np.min(own))
            elif self.engine.wave_walls:
                # never-run bucket: other buckets' walls are the wrong
                # scale (a small bucket's wall would UNDERestimate a large
                # one and defer its deadline cuts past rescue), so clamp
                # the cross-bucket fallback to at least cold_start_wall
                seed = max(float(np.min(self.engine.wave_walls)),
                           self.cold_start_wall)
            else:
                seed = None
            est = _EwmaWall(self.ewma_alpha, seed, self.cold_start_wall)
            self._ewma[bucket] = est
        return est

    def lane_estimate(self, lane: int) -> float:
        """Current EWMA wave-wall estimate for dispatch ``lane`` (seconds):
        the walls of the waves that lane has pulled so far."""
        return self._lane_ewma[lane].value

    def group_estimate(self, size: int) -> float:
        """Current EWMA wave-wall estimate (seconds) for waves dispatched
        on a ``size``-device group (resize mode observability)."""
        return self._size_wall(size).value

    def _size_wall(self, size: int) -> _EwmaWall:
        est = self._group_ewma.get(size)
        if est is None:
            own = self.engine.group_walls.get(size)
            seed = float(np.min(own)) if own else None
            est = _EwmaWall(self.ewma_alpha, seed, self.cold_start_wall)
            self._group_ewma[size] = est
        return est

    @property
    def pipeline_depth(self) -> int:
        """Waves actually kept in flight at once.  Shared-mesh lanes cap
        at two whatever the lane count -- depth 2 already hides all host
        prep behind device compute, and deeper queues only pile programs
        onto the shared device set (lanes are device groups of ONE mesh,
        not disjoint hardware).  Resize mode lifts the cap to ``n_lanes``:
        disjoint groups ARE separate hardware, and ``_dispatch`` keeps at
        most one wave in flight per group anyway.  ``wait_bound`` packs
        over this same depth so the slack model matches what
        ``_dispatch`` really does."""
        if self._resize:
            return self.n_lanes
        return min(self.n_lanes, 2)

    # -- wave cutting -------------------------------------------------------
    def wait_bound(self, bucket: int) -> float:
        """Worst-case wait (seconds) for a wave cut from ``bucket`` NOW.

        Single lane: the bucket's estimated wall plus one estimated wave
        from every OTHER bucket with queued work (those waves may cut in
        the same tick and be packed first), scaled by ``slack_margin``.

        Multi-lane: the LPT makespan of the same waves packed over the
        ACTUAL in-flight concurrency (``pipeline_depth``, not the lane
        count -- modeling more concurrency than ``_dispatch`` provides
        would defer deadline cuts past rescue), with each wave costed at
        no less than the average per-lane EWMA wall.  Lane walls are
        measured launch->ready, so when in-flight waves contend on the
        shared device set they inflate and the bound converges back
        toward the serial sum; with no contention they stay at the device
        wall and the bound tightens honestly.

        Resize mode: the same waves are packed longest-first over the
        device groups ``plan_groups`` would cut for them right now --
        heterogeneous lane capacities, each wave costed at no less than
        its group's per-SIZE EWMA wall.  A single-group plan (``n_lanes=1``
        full mesh) degenerates to the plain serial sum, exactly the
        shared-mesh single-lane bound.
        """
        costs = [self.estimate(bucket)]
        for b, q in self._queues.items():
            if b != bucket and q:
                costs.append(self.estimate(b))
        return self._pack_bound(costs) * self.slack_margin

    def _pack_bound(self, costs: List[float]) -> float:
        """Predicted finish (seconds, UNSCALED) of ``costs`` estimated wave
        walls packed over the dispatch concurrency -- the one pack model
        behind :meth:`wait_bound`, :meth:`backlog_bound`, and
        :meth:`admission_estimate`.  Shared mesh: LPT over
        ``pipeline_depth`` with the average per-lane EWMA wall as a
        per-wave floor (serial sum with one lane).  Resize: heterogeneous
        LPT over the groups ``plan_groups`` would cut, floored by the
        per-SIZE EWMA walls."""
        if not costs:
            return 0.0
        if self._resize:
            k = min(len(costs), self.n_devices, self.n_lanes)
            if k == 1:
                return float(sum(costs))
            sizes = plan_groups(self.n_devices,
                                sorted(costs, reverse=True),
                                self.engine.slots, max_groups=self.n_lanes)
            finish = [0.0] * k
            for c in sorted(costs, reverse=True):
                g = min(range(k), key=lambda j: (finish[j], j))
                finish[g] += max(c, self._size_wall(sizes[g]).value)
            return max(finish)
        if self.n_lanes == 1:
            return float(sum(costs))
        lane_wall = float(np.mean([e.value for e in self._lane_ewma]))
        return core_scheduler.schedule_lpt(
            [max(c, lane_wall) for c in costs], self.pipeline_depth).makespan

    def backlog_bound(self) -> float:
        """Predicted seconds to clear the ENTIRE queue as of now: every
        implied wave (``ceil(queued / slots)`` per bucket, partials
        included) packed over the dispatch concurrency.  This is the
        overload pressure gauge -- :meth:`poll` sheds at-risk queued work
        when it exceeds ``pressure_threshold`` -- and it is NOT scaled by
        ``slack_margin`` (a raw completion estimate, not a cut trigger).
        Wave counts divide by the MEASURED occupancy EWMA, not ``slots``:
        under deadline pressure waves cut partial, and modeling full
        occupancy would underestimate time-to-clear exactly when the
        gauge matters most.  Each wave is floored by the measured
        server-level wall-clock per wave (host prep included), not just
        the device wall.  ``0.0`` with an empty queue."""
        costs: List[float] = []
        per_wave = self._per_wave()
        floor = self._wave_floor.value
        for b, q in self._queues.items():
            if q:
                n_waves = math.ceil(len(q) / per_wave)
                costs.extend([max(self.estimate(b), floor)] * n_waves)
        return self._pack_bound(costs)

    def _per_wave(self) -> float:
        """Effective requests per dispatched wave: the occupancy EWMA
        observed on real waves (seeded at ``slots``), clamped to [1,
        slots].  The backlog and admission models count implied waves
        against THIS, so partial-wave regimes (deadline cuts under
        overload) feed back into honest, larger clear-time predictions."""
        return min(float(self.engine.slots), max(1.0, self._occupancy.value))

    def admission_estimate(self, bucket: int, cost: float = 0.0) -> float:
        """Predicted seconds until a request submitted to ``bucket`` RIGHT
        NOW has its result: the queue backlog's implied waves plus the
        request's own wave, packed over the dispatch concurrency.  The own
        wave costs the bucket's EWMA estimate floored by the request's
        calibrated Analyzer cost (``CostCalibration``: measured
        seconds-per-cost-unit), so an unusually expensive request in a
        cheap bucket is predicted honestly even before its wave ever ran.
        In the own bucket only the FULL waves queue ahead -- the request
        itself rides the trailing partial wave.  Wave counts divide by
        the measured occupancy EWMA (see :meth:`_per_wave`) and every
        wave is floored by the measured server-level wall-clock per wave,
        so admission stays honest when overload degrades waves to partial
        cuts or host overhead dominates the device wall.  Unscaled
        (classification headroom is ``admit_margin``'s job, not
        ``slack_margin``'s)."""
        floor = self._wave_floor.value
        own = max(self.estimate(bucket), self._calib.seconds(cost, 0.0),
                  floor)
        costs = [own]
        per_wave = self._per_wave()
        for b, q in self._queues.items():
            if not q:
                continue
            n_waves = (int(len(q) // per_wave) if b == bucket
                       else math.ceil(len(q) / per_wave))
            costs.extend([max(self.estimate(b), floor)] * n_waves)
        return self._pack_bound(costs)

    def _shed_pressure(self, now: float, bound: float) -> None:
        """Degrade under load (DESIGN.md section 15): once the backlog
        bound exceeds ``pressure_threshold``, shed EVERY at-risk queued
        request -- ``deadline`` set and predicted to miss at the current
        bound -- lowest class first, newest-first within a class (the
        oldest have the most invested wait).  The bound is recomputed
        after each shed, so the at-risk set shrinks honestly: shedding
        the doomed tail restores slack to the survivors, and the loop
        stops when nobody left is predicted to miss (NOT merely when the
        gauge dips under the threshold -- a sub-threshold backlog can
        still doom a request whose own slack is shorter).  Shed entries
        are accounted exactly like door sheds (``class_stats``,
        ``shed_log``), never silently dropped; deadline-less requests are
        never pressure-shed."""
        if bound <= self.pressure_threshold:
            return
        while True:
            at_risk = [e for q in self._queues.values() for e in q
                       if e.deadline is not None and e.deadline - now < bound]
            if not at_risk:
                return
            victim = min(at_risk, key=lambda e: (e.priority, -e.seq))
            self._queues[victim.bucket].remove(victim)
            stats = self._stats_for(victim.tenant, victim.priority)
            stats.shed += 1
            self.shed_under_pressure += 1
            self.shed_log.append(victim.ticket)
            bound = self.backlog_bound()

    def _cut_reason(self, bucket: int, queue: List[QueuedRequest],
                    now: float) -> Optional[str]:
        """Why the FRONT of ``queue`` should be cut right now, if at all."""
        if not queue:
            return None
        if len(queue) >= self.engine.slots:
            return "full"
        # min over ALL arrivals, not queue[0]: class ordering may have
        # moved a newer high-priority entry to the front
        oldest = min(e.arrival for e in queue)
        # a forced cut takes the whole (sub-slots) queue, so deadline
        # pressure from ANY queued request -- not just the head -- cuts:
        # a tight deadline queued behind a loose one must not be starved
        # by FIFO position.
        deadlines = [e.deadline for e in queue if e.deadline is not None]
        if deadlines:
            slack = min(deadlines) - now
            if slack <= self.wait_bound(bucket):
                return "deadline"
        # adaptive batching patience: a partial wave older than (roughly)
        # one wave wall has nothing left to gain from waiting -- and
        # max_wait stays the absolute starvation-freedom backstop
        patience = min(self.max_wait,
                       self.batch_patience * self.estimate(bucket))
        if now - oldest >= patience:
            return "age"
        return None

    def _class_order(self, queue: List[QueuedRequest],
                     now: float) -> List[QueuedRequest]:
        """Wave-composition order for one bucket queue: highest effective
        class first, FIFO (seq) within a class.  The effective class is
        the submitted priority, boosted above every real class once the
        entry has waited ``max_wait`` (the per-class starvation backstop:
        a stream of high-priority arrivals keeps cutting full waves ahead
        of a low-priority entry until it ages, then it jumps the wave).
        Single-class un-aged queues come back UNCHANGED -- pre-overload
        wave composition, bit for bit."""
        effs = [math.inf if now - e.arrival >= self.max_wait
                else float(e.priority) for e in queue]
        if all(x == effs[0] for x in effs):
            return queue
        order = sorted(range(len(queue)),
                       key=lambda i: (-effs[i], queue[i].seq))
        return [queue[i] for i in order]

    def _shed_doomed(self, bucket: int, queue: List[QueuedRequest],
                     now: float) -> List[QueuedRequest]:
        """Under ``shed="predicted-miss"``, drop queued entries that can no
        longer hit: remaining slack below their own wave's wall (EWMA
        estimate floored by the measured server-level wall-clock, with the
        same ``slack_margin`` headroom deadline cuts use -- the wall is an
        estimate, and an entry inside its error band is a miss in
        expectation).  Dispatching such an entry only converts a shed into
        a guaranteed miss while burning a slot a live request could use.
        Accounted exactly like pressure sheds; deadline-less entries never
        qualify.  A no-op under every other policy -- ``shed="never"``
        chases every admitted request to the end, late or not."""
        if self.shed != "predicted-miss":
            return queue
        wall = (max(self.estimate(bucket), self._wave_floor.value)
                * self.slack_margin)
        kept: List[QueuedRequest] = []
        for e in queue:
            if e.deadline is None or e.deadline - now >= wall:
                kept.append(e)
                continue
            stats = self._stats_for(e.tenant, e.priority)
            stats.shed += 1
            self.shed_under_pressure += 1
            self.shed_log.append(e.ticket)
        return kept

    def _cut_ready(self, now: float, *, drain: bool = False
                   ) -> List[tuple]:
        """Cut every currently-cuttable wave; returns [(bucket, entries,
        reason, cut_at)] with queues updated in place."""
        ready = []
        for bucket, queue in self._queues.items():
            queue = self._shed_doomed(bucket, queue, now)
            queue = self._class_order(queue, now)
            while True:
                reason = "drain" if drain and queue else None
                reason = self._cut_reason(bucket, queue, now) or reason
                if reason is None:
                    break
                wave, queue = self.engine.cut_wave(
                    queue, force=reason != "full")
                if not wave:
                    break
                ready.append((bucket, wave, reason, now))
            self._queues[bucket] = queue
        return ready

    def _wave_weight(self, wave: List[QueuedRequest]) -> float:
        """Class weight of a cut wave for the weighted-fair launch order:
        ``priority_weight ** p`` for the wave's highest priority ``p``
        (exponent clamped to +-64 so pathological priorities cannot
        overflow).  All-default-priority waves weigh 1.0 exactly."""
        p = max(e.priority for e in wave)
        return float(self.priority_weight) ** max(-64, min(64, p))

    def _pack_order(self, ready: List[tuple]) -> List[tuple]:
        """Weighted-fair cross-bucket packing: urgent (deadline/age) cuts
        first, then ``core.scheduler.schedule_weighted`` over the EWMA
        wall estimates with the waves' class weights -- a high-priority
        wave launches ahead of an equal-cost best-effort one, while a
        long-enough low-priority wave still launches early (weighted
        fairness, not strict priority).  With all priorities at the
        default the weights are all 1.0 and the order is exactly the
        pre-overload ``schedule_lpt`` one."""
        if len(ready) <= 1:
            return ready

        def wlpt(group: List[tuple]) -> List[tuple]:
            if len(group) <= 1:
                return group
            costs = [self.estimate(bucket) for bucket, _, _, _ in group]
            weights = [self._wave_weight(wave) for _, wave, _, _ in group]
            order = core_scheduler.schedule_weighted(
                costs, weights, 1).assignment[0]
            return [group[i] for i in order]

        urgent = [r for r in ready if r[2] in ("deadline", "age")]
        rest = [r for r in ready if r[2] not in ("deadline", "age")]
        return wlpt(urgent) + wlpt(rest)

    # -- scheduler tick -----------------------------------------------------
    def poll(self) -> List[GraphResult]:
        """One scheduler tick: cut, pack, dispatch, stream.

        Cuts every wave that is ready at the current clock (full waves,
        deadline-pressured partials, over-age partials), dispatches them in
        packed order through ``engine.dispatch_wave``, and returns the
        newly completed results -- each stamped with its ``deadline`` and
        wave-completion ``completed_at``.  Returns ``[]`` when nothing was
        ready; callers loop ``poll`` between arrivals.

        Every tick first reads the backlog pressure gauge
        (:meth:`backlog_bound`; the peak is kept on ``peak_pressure``)
        and, above ``pressure_threshold``, sheds at-risk queued work
        lowest-class-first (:meth:`_shed_pressure`) before cutting.
        """
        with spans.span("serve.tick"):
            now = self.clock()
            with spans.span("serve.cut"):
                pressure = self.backlog_bound()
                if pressure > self.peak_pressure:
                    self.peak_pressure = pressure
                if pressure > self.pressure_threshold:
                    self._shed_pressure(now, pressure)
                ready = self._cut_ready(now)
            return self._route(self._dispatch(ready))

    def drain(self) -> List[GraphResult]:
        """Force-flush: cut everything still queued (partial waves allowed,
        reason ``"drain"``), dispatch in packed order, return the results.
        The queue is empty afterwards."""
        with spans.span("serve.tick"):
            with spans.span("serve.cut"):
                ready = self._cut_ready(self.clock(), drain=True)
            return self._route(self._dispatch(ready))

    def _launch(self, bucket: int, wave: List[QueuedRequest],
                submesh=None) -> InFlightWave:
        """Launch one cut wave (``engine.begin_wave``); under a profiler,
        count its requests and the seconds they waited in the queue."""
        if spans.enabled():
            now = self.clock()
            spans.count("serve.requests_launched", len(wave))
            spans.count("serve.queue_wait_s",
                        sum(now - e.arrival for e in wave))
        return self.engine.begin_wave(bucket, [e.request for e in wave],
                                      submesh=submesh)

    def _harvest(self, lane: int, wave: List[QueuedRequest], reason: str,
                 cut_at: float, handle: InFlightWave,
                 results: List[GraphResult], prev_done: list) -> None:
        """Block on one launched wave and deliver it into ``results``.

        Its measured wall feeds the bucket EWMA and the pulling lane's
        (per lane index, or per group size in resize mode), the
        calibration and the admission floor; the wave is logged and each
        result stamped and accounted.  ``prev_done`` holds the tick's
        previous harvest time and is advanced."""
        with spans.span("serve.harvest", wave=handle.index):
            wave_results = self.engine.finish_wave(handle)
            done_at = self.clock()
            wall = self.engine.bucket_walls[handle.bucket][-1]
            self._ewma_for(handle.bucket).observe(wall)
            if self._resize:
                self._size_wall(handle.pending.lanes).observe(wall)
            else:
                self._lane_ewma[lane].observe(wall)
            self._calib.observe(sum(e.cost for e in wave), wall)
            self._occupancy.observe(len(wave))
            # MARGINAL wall-clock for this wave: waves cut in the same
            # tick dispatch back-to-back, so (done - cut) of a later wave
            # includes its predecessors' walls and would inflate the
            # admission floor several-fold at steady load
            start = (cut_at if prev_done[0] is None
                     else max(cut_at, prev_done[0]))
            self._wave_floor.observe(done_at - start)
            prev_done[0] = done_at
            self.dispatch_log.append(WaveLog(
                handle.bucket, len(wave), reason, cut_at, wall, lane,
                group_size=handle.pending.lanes,
                classes=self._wave_classes(wave)))
            self.dispatched += len(wave)
            for entry, res in zip(wave, wave_results):
                res.deadline = entry.deadline
                res.completed_at = done_at
                self._account_delivery(entry, done_at)
                results.append(res)

    def _dispatch(self, ready: List[tuple]) -> List[GraphResult]:
        """Dispatch the tick's cut waves over the ``n_lanes`` lanes.

        Each wave is pulled by the earliest-idle lane (greedy Algorithm-8
        queue over the per-bucket estimates; deterministic under a fake
        clock).  Waves stay IN FLIGHT via the engine's
        ``begin_wave``/``finish_wave`` split -- a lane launches its wave
        while earlier waves still execute, so host padding overlaps device
        compute -- but the pipeline depth is capped at TWO regardless of
        lane count: depth 2 already hides all host prep behind device
        compute, and deeper queues only pile programs onto the shared
        device set (lanes are device *groups* of one mesh here, not
        disjoint hardware), measurably hurting wave walls.  Waves are
        harvested in launch order; the measured launch->ready wall feeds
        both the bucket EWMA and the pulling lane's EWMA (the contention
        signal ``wait_bound`` reads).  With one lane this degenerates to
        the serial launch-then-finish loop.

        Resize mode routes to :meth:`_dispatch_groups` instead: lanes
        become disjoint device groups replanned per tick.
        """
        if self._resize:
            return self._dispatch_groups(ready)
        # start from any results stranded by a previously failed tick;
        # harvest appends into this same list, so even if THIS tick fails
        # mid-dispatch, everything harvested stays in _undelivered and the
        # next tick returns it
        results = self._undelivered
        lane_busy = [0.0] * self.n_lanes
        depth = self.pipeline_depth
        in_flight: List[tuple] = []        # (lane, est, wave-entries,
        #                                     reason, cut_at, InFlightWave)
        prev_done = [None]                 # last harvest time THIS tick

        def harvest(item) -> None:
            lane, est, *launched = item
            lane_busy[lane] -= est         # the lane is free again
            self._harvest(lane, *launched, results, prev_done)

        try:
            for bucket, wave, reason, cut_at in self._pack_order(ready):
                # last-moment doomed check: earlier waves in this tick may
                # have pushed the clock past this wave's remaining slack
                wave = self._shed_doomed(bucket, wave, self.clock())
                if not wave:
                    continue
                while len(in_flight) >= depth:
                    harvest(in_flight.pop(0))
                # earliest-idle lane; ties rotate from _next_lane so every
                # lane pulls waves (and keeps its EWMA wall live) even when
                # ticks cut one wave at a time
                lane = min(range(self.n_lanes),
                           key=lambda l: (lane_busy[l],
                                          (l - self._next_lane)
                                          % self.n_lanes))
                self._next_lane = (lane + 1) % self.n_lanes
                est = self.estimate(bucket)
                handle = self._launch(bucket, wave)
                lane_busy[lane] += est
                in_flight.append((lane, est, wave, reason, cut_at, handle))
        finally:
            # a begin_wave failure mid-tick must not abandon the waves
            # already in flight: harvest them so their results stream
            # (via _undelivered if the exception propagates), the engine
            # counters stay consistent, and open-loop pollers don't hang
            # on requests that silently vanished
            while in_flight:
                harvest(in_flight.pop(0))
        self._undelivered = []
        return results

    def _dispatch_groups(self, ready: List[tuple]) -> List[GraphResult]:
        """Resize-mode dispatch: disjoint per-lane device groups, replanned
        between waves from queue composition (DESIGN.md section 14).

        The tick's cut waves are costed by their bucket EWMA estimates and
        handed to ``plan_groups``: the i-th largest wave is paired with the
        i-th widest group (a huge-graph wave grabs the wide group while
        small waves pack one device each), overflow waves go to the
        earliest-finishing group (heterogeneous LPT -- the same packing
        ``wait_bound`` models).  Every wave launches via
        ``begin_wave(submesh=...)`` on its group's devices ONLY, so groups
        execute in genuine parallel; at most one wave is in flight per
        group (a group's next wave first harvests its previous one).
        Measured walls feed the bucket EWMA and the group-SIZE EWMA
        (``group_estimate``); ``dispatch_log`` records the pulling group
        index and its width, ``last_group_sizes`` the tick's plan.
        """
        results = self._undelivered
        packed = self._pack_order(ready)
        if not packed:
            self._undelivered = []
            return results
        ests = [self.estimate(bucket) for bucket, _, _, _ in packed]
        # autoscale: re-pick the concurrent group count each tick from the
        # per-size EWMA walls instead of always spreading to n_lanes -- a
        # lone huge wave collapses to one wide group (whose measured wall
        # is lower), a deep backlog of small waves spreads out again
        max_lanes = self.n_lanes
        if self._autoscale:
            max_lanes = plan_lanes(self.n_devices, ests, self.engine.slots,
                                   self.n_lanes,
                                   size_wall=self.group_estimate)
            self.last_auto_lanes = max_lanes
        sizes = plan_groups(self.n_devices, sorted(ests, reverse=True),
                            self.engine.slots, max_groups=max_lanes)
        groups = dist_sharding.partition_mesh(self.engine.mesh, sizes)
        self.last_group_sizes = list(sizes)
        k = min(len(packed), self.n_devices, max_lanes)
        # wave -> group: demand-descending waves greedily take the
        # earliest-finishing of the k demand-assigned groups (ties toward
        # the wider group -- plan_groups sizes are descending), so the
        # first k waves get distinct groups largest<->largest and overflow
        # piles LPT-style onto whichever group frees up first
        group_busy = [0.0] * k
        assign: Dict[int, int] = {}
        order = sorted(range(len(packed)), key=lambda i: (-ests[i], i))
        for i in order:
            g = min(range(k), key=lambda j: (group_busy[j], j))
            group_busy[g] += max(ests[i], self._size_wall(sizes[g]).value)
            assign[i] = g
        in_flight: Dict[int, tuple] = {}    # group -> (wave-entries,
        #                                      reason, cut_at, InFlightWave)
        prev_done = [None]                 # last harvest time THIS tick

        def harvest(g: int) -> None:
            self._harvest(g, *in_flight.pop(g), results, prev_done)

        try:
            for i, (bucket, wave, reason, cut_at) in enumerate(packed):
                # last-moment doomed check (see _dispatch)
                wave = self._shed_doomed(bucket, wave, self.clock())
                if not wave:
                    continue
                g = assign[i]
                if g in in_flight:          # one wave per group at a time
                    harvest(g)
                handle = self._launch(bucket, wave, submesh=groups[g])
                in_flight[g] = (wave, reason, cut_at, handle)
        finally:
            # mirror _dispatch: a begin_wave failure must not abandon
            # in-flight waves -- harvest them all so results stream (via
            # _undelivered if the exception propagates)
            while in_flight:
                harvest(min(in_flight))
        self._undelivered = []
        return results

    # -- warmup -------------------------------------------------------------
    def warmup(self, sizes: Sequence[int]) -> None:
        """Pre-compile + pre-trace the buckets for ``sizes`` vertex counts
        by dispatching one dummy single-request wave per NEW bucket, so the
        first real request doesn't eat compile/trace time -- and so the
        EWMA seeds from a measured steady-state wall (the second dispatch;
        ``_ewma_for``'s min-seed ignores the first wave's trace outlier).

        Resize mode additionally warms every device-group PLACEMENT the
        plan can reach for those buckets: XLA compiles one executable per
        placement (the abstract-mesh trace is shared across equal-size
        groups, the binary is not), and the double dispatch keeps the
        ``group_walls`` min -- the per-size EWMA seed behind
        :meth:`group_estimate` and the resize ``wait_bound`` -- at the
        steady-state wall instead of the compile outlier.
        """
        req = GraphRequest(np.eye(2, dtype=np.float32),
                           np.zeros((2, self.engine.f_in), np.float32),
                           request_id=-1)
        buckets = sorted({self.engine.bucket_for(int(n)) for n in sizes})
        for n in buckets:
            if n in self.engine.bucket_walls:
                continue
            self.engine.dispatch_wave(n, [req])
            # a second dispatch records the steady-state (traced) wall
            self.engine.dispatch_wave(n, [req])
        if not self._resize:
            return
        # placement warm covers ALL requested buckets, not just fresh ones:
        # an engine warmed by plain serve() has bucket walls but no submesh
        # executables, and re-warming a compiled placement is just two
        # cheap cache-hit dispatches
        size = 1
        while size <= self.n_devices:
            if self.engine.slots % size == 0:
                n_groups = self.n_devices // size
                part = ([size] * n_groups
                        + [1] * (self.n_devices - size * n_groups))
                subs = dist_sharding.partition_mesh(self.engine.mesh, part)
                for sub in subs[:n_groups]:
                    for n in buckets:
                        for _ in range(2):
                            self.engine.finish_wave(self.engine.begin_wave(
                                n, [req], submesh=sub))
            size *= 2
