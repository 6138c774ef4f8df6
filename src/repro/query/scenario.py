"""MultiQueryScenario: N concurrent tracking queries through ONE pipeline.

The single-query platform activates a spotlight of cameras and routes their
frames through the shared FC -> VA -> CR -> UV dataflow.  This driver makes
*a set of concurrent queries* the served unit while keeping the pipeline
singular:

* **Union sourcing** — each tick sources one frame per camera in the
  *union* of the live queries' applied spotlights.  A camera wanted by ten
  queries costs one event, not ten: per-event cost grows with O(union
  active cameras), not O(N x cameras).
* **Query tagging** — every sourced event carries a ``query_mask`` bit per
  interested live query; the runtime's 1:1 fast paths reuse event objects,
  so the tag rides for free through VA/CR to the sink, where completions
  (and, via the compiled app's drop hook, drops at all three drop points)
  are charged **per query**.
* **Fused analytics** — with embeddings enabled, each VA batch runs ONE
  query-major ``reid_match_multi`` dispatch over all live query embeddings
  (per-pair tenancy mask), instead of one dispatch per query.  The VA hook
  and the rule for when it reads an answer are the single-query driver's
  (``TrackingScenario._va_reid``); this driver supplies the live block, the
  tenancy mask and the per-query credit.  With
  ``spotlight_mode="kernel"`` the blind-spot queries' balls are computed by
  ONE multi-source ``spotlight_ball`` invocation
  (:func:`repro.core.tracking.multi_source_spotlight` — the same
  implementation backing ``TLProbabilistic.spotlight_multi``).
* **Admission control** — an optional
  :class:`~repro.query.admission.AdmissionController` queues/rejects
  submissions while the CR completion budget (sampled by the PR-4
  telemetry plane) is degraded, shedding load so admitted queries keep
  their QoS.

Bit-exactness contract (the tenancy plane's correctness anchor): with
interference disabled — admission off, and every query identical and
submitted at t=0 so the union equals each query's own spotlight — the fused
run's *per-query* summaries are **bit-identical** to N independent
single-query ``TrackingScenario`` runs, drops on or off.  ``tests/
test_query.py`` freezes this as a golden; the hypothesis suite checks the
lifecycle/accounting invariants under arbitrary submit/cancel schedules.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import (
    TYPE_CHECKING,
    Any,
    Dict,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
    Union,
)

if TYPE_CHECKING:  # pragma: no cover - typing only (avoids the jax-heavy
    # repro.serving package import at module load)
    from repro.serving.journal import Journal

import numpy as np

from repro.core.budget import TaskBudget
from repro.core.clock import span
from repro.core.events import Event
from repro.core.pipeline import DP_FAULT
from repro.core.tracking import TLProbabilistic, TLWBFS, multi_source_spotlight
from repro.sim.scenario import ScenarioConfig, ScenarioResult, TrackingScenario

from .admission import AdmissionController, AdmissionPolicy
from .registry import QueryRegistry, QuerySpec, QueryState

__all__ = [
    "MultiQueryScenario",
    "MultiQueryResult",
    "normalize_queries",
    "run_queries_serial",
]


def normalize_queries(
    queries: Union[int, Sequence[QuerySpec]]
) -> List[QuerySpec]:
    """``N`` -> N default (identical, t=0) queries; a sequence passes
    through.  Identical default queries are the scaling benchmark's shape:
    many users tracking the same entity, deduplicated by the fused plane."""
    if isinstance(queries, int):
        if queries < 1:
            raise ValueError(f"need at least one query, got {queries}")
        return [QuerySpec() for _ in range(queries)]
    out = list(queries)
    if not out:
        raise ValueError("need at least one query")
    for q in out:
        if not isinstance(q, QuerySpec):
            raise TypeError(f"expected QuerySpec, got {type(q).__name__}")
    return out


def _zero_xi(b: int) -> float:
    return 0.0


@dataclass
class MultiQueryResult:
    """Fused-run outputs: the global (shared-pipeline) result plus the
    per-query views and the registry/admission state."""

    result: ScenarioResult
    per_query: Dict[int, ScenarioResult]
    registry: QueryRegistry
    admission: Optional[AdmissionController] = None
    states: Dict[int, str] = field(default_factory=dict)

    def per_query_summary(self, qid: int) -> Dict[str, float]:
        """Summary of one query's view — with interference disabled this is
        bit-identical to the query's solo ``TrackingScenario`` summary."""
        return self.per_query[qid].summary()

    def summary(self) -> Dict[str, Any]:
        reg = self.registry
        out = dict(self.result.summary())
        out["queries"] = len(self.per_query)
        out["queries_live_end"] = reg.live_count()
        out["queries_found"] = sum(
            1 for s in reg.states.values() if s.found_at is not None
        )
        # The global timeline is the union spotlight: its peak/mean are the
        # tenancy plane's cost metric (vs sum of per-query actives).
        sizes = [c for _, c in self.result.active_timeline]
        out["union_peak_active"] = self.result.peak_active
        out["union_mean_active"] = (
            round(float(np.mean(sizes)), 2) if sizes else 0.0
        )
        per_q_sourced = sum(s.sourced for s in reg.states.values())
        out["per_query_sourced_sum"] = per_q_sourced
        if self.admission is not None:
            out.update(self.admission.stats())
            out["adm_submitted"] = reg.submitted
        return out


class MultiQueryScenario(TrackingScenario):
    """Drive N concurrent queries through one compiled app.

    ``queries`` is an int (N identical default queries) or a sequence of
    :class:`QuerySpec`.  ``admission`` is an
    :class:`~repro.query.admission.AdmissionPolicy` /
    :class:`~repro.query.admission.AdmissionController` (None admits
    everything).  ``spotlight_mode`` is ``"per-query"`` (each query's own
    TL strategy instance, the bit-exactness reference) or ``"kernel"``
    (blind-spot balls batched into one multi-source ``spotlight_ball``
    dispatch; weighted-ball TLs only, bit-equal for TLWBFS).
    """

    def __init__(
        self,
        config: ScenarioConfig,
        queries: Union[int, Sequence[QuerySpec]],
        *,
        admission: Union[AdmissionPolicy, AdmissionController, None] = None,
        spotlight_mode: str = "per-query",
        app: Any = None,
        deployment: Any = None,
        journal: Optional["Journal"] = None,
        mesh: Any = None,
    ) -> None:
        if spotlight_mode not in ("per-query", "kernel"):
            raise ValueError(f"unknown spotlight_mode {spotlight_mode!r}")
        self._spotlight_mode = spotlight_mode
        #: Optional ``distributed.MeshRules`` handle (see
        #: ``distributed.camera_mesh``): with ``engine="megastep"`` the
        #: device backend shards the camera-block world over the mesh's
        #: ``cameras`` axis (``kernels.megastep.sharded``), bit-identically
        #: to the single-shard scan.  The registry itself stays replicated —
        #: every shard sees all query tag bits/tables — and the per-query
        #: budget counters come back all-reduced on the chunk cadence.
        self.mesh_rules = mesh
        #: Optional append-only journal + snapshot ring
        #: (:class:`repro.serving.journal.Journal`): the accounting hooks
        #: record the observable event stream, and a periodic tick appends
        #: frontier snapshots for crash recovery.  None costs one attribute
        #: test per hook invocation.
        self.journal = journal
        self.registry = QueryRegistry()
        if isinstance(admission, AdmissionPolicy):
            admission = AdmissionController(admission)
        self.admission: Optional[AdmissionController] = admission
        self._started = False
        self._specs = normalize_queries(queries)

        super().__init__(config, app=app, deployment=deployment)

        # Undo the single-query seeding the parent applied from the app's
        # template TL: the union mirrors start empty and are rebuilt from
        # the t=0 submissions below.
        self.compiled.fc_active.clear()
        self._ctrl_target = set()
        self._mask_of = {}
        self._source_hook = self._on_sourced
        self._pending_masks: List[int] = []
        self.compiled.install_drop_hook(self._on_pipeline_drop)

        t_q = time.perf_counter()
        for spec in self._specs:
            st = self.registry.register(spec, now=max(spec.submit_at, 0.0))
            if spec.cancel_at is not None:
                self.sim.schedule_at(
                    spec.cancel_at, self._cancel_query, st.query_id, "cancelled"
                )
            if spec.ttl_s is not None:
                self.sim.schedule_at(
                    max(spec.submit_at, 0.0) + spec.ttl_s,
                    self._expire_query,
                    st.query_id,
                )
            if spec.submit_at <= 0.0:
                self._submit_query(st.query_id)
            else:
                self.sim.schedule_at(spec.submit_at, self._submit_query, st.query_id)
        self.build_seconds += time.perf_counter() - t_q

    # ------------------------------------------------------------------ #
    # Lifecycle: submit -> scoped -> found -> expired/cancelled           #
    # ------------------------------------------------------------------ #
    def _submit_query(self, qid: int) -> None:
        st = self.registry.get(qid)
        if st.dead or st.live:
            return  # cancelled while pending, or double submission
        ctrl = self.admission
        if ctrl is not None:
            verdict = ctrl.decide(self, self.registry.live_count())
            if verdict == "queue":
                ctrl.queue.append(qid)
                self.registry.queued_peak = max(
                    self.registry.queued_peak, len(ctrl.queue)
                )
                return
            if verdict == "reject":
                self.registry.rejected += 1
                self.registry.mark(
                    st, "cancelled", self.sim.time, reason="admission-rejected"
                )
                return
        self.registry.admitted += 1
        self._activate_query(st, immediate=not self._started)

    def _activate_query(self, st: QueryState, immediate: bool) -> None:
        spec, cfg = st.spec, self.cfg
        now = self.sim.time
        if spec.make_tl is not None:
            tl = spec.make_tl(self.world, self.cameras)
        else:
            tl = spec.solo_config(cfg).make_tl(
                self.world.road, self.cameras.camera_vertices
            )
        if spec.coverage is not None and hasattr(tl, "coverage"):
            tl.coverage = float(spec.coverage)
        if self._spotlight_mode == "kernel" and not isinstance(
            tl, (TLWBFS, TLProbabilistic)
        ):
            raise ValueError(
                "spotlight_mode='kernel' needs weighted-ball TLs "
                f"(TLWBFS/TLProbabilistic); query {st.query_id} uses "
                f"{type(tl).__name__}"
            )
        if tl.last_seen_camera is None:
            # Same seeding rule as the single-query scenario: the nearest
            # camera to the entity's position (at t=0 that is the walk's
            # start vertex — byte-for-byte the solo `_seed_tl`).
            if spec.last_seen_camera is not None:
                tl.last_seen_camera = spec.last_seen_camera
            else:
                cams = self.cameras.camera_vertices
                cam_ids = list(cams)
                cam_pos = self.road.positions[
                    np.fromiter(cams.values(), dtype=np.int64)
                ]
                if now <= 0.0:
                    pos = self.road.positions[self.walk.vertices[0]]
                else:
                    pos = self.walk.position(now)
                d = np.linalg.norm(cam_pos - pos, axis=1)
                tl.last_seen_camera = cam_ids[int(np.argmin(d))]
            tl.last_seen_time = now
            tl.active = tl.spotlight(now)
        st.tl = tl
        st.budget = TaskBudget(f"Q{st.query_id}", _zero_xi, m_max=1)
        if cfg.embed_dim:
            if spec.embedding_seed is None:
                st.embedding = self.cameras.entity_embedding
            else:
                rng = np.random.default_rng(spec.embedding_seed)
                st.embedding = rng.normal(size=(cfg.embed_dim,)).astype(np.float32)
        self.registry.mark(st, "scoped", now)
        st.requested = set(tl.active)
        if immediate:
            # Pre-run activation: applied instantly, exactly like the solo
            # scenario's initial active set (no control latency at t=0).
            for cam in st.requested:
                self._apply_query_active(st.query_id, cam, True)
            self.compiled.fc_active |= st.requested
            self._ctrl_target |= st.requested
        else:
            lat = self.sim.network.man_latency_s
            sched = self.sim.schedule
            for cam in sorted(st.requested):
                sched(lat, self._apply_query_active, st.query_id, cam, True)
            set_active = self.compiled.set_fc_active
            for cam in sorted(st.requested - self._ctrl_target):
                sched(lat, set_active, cam, True)
            self._ctrl_target |= st.requested

    def cancel(self, qid: int, reason: str = "cancelled") -> None:
        """Cancel a query now (or schedule via ``QuerySpec.cancel_at``)."""
        self._cancel_query(qid, reason)

    def _cancel_query(self, qid: int, reason: str = "cancelled") -> None:
        st = self.registry.get(qid)
        if st.dead:
            return
        ctrl = self.admission
        if ctrl is not None and qid in ctrl.queue:
            ctrl.queue.remove(qid)
        was_live = st.live
        self.registry.mark(st, "cancelled", self.sim.time, reason=reason)
        if was_live:
            self._end_query_control(st)

    def _expire_query(self, qid: int) -> None:
        st = self.registry.get(qid)
        if st.dead or st.state == "found":
            return  # found queries keep tracking; ttl only bounds the search
        ctrl = self.admission
        if ctrl is not None and qid in ctrl.queue:
            ctrl.queue.remove(qid)
        was_live = st.live
        self.registry.mark(st, "expired", self.sim.time, reason="ttl")
        if was_live:
            self._end_query_control(st)

    def _end_query_control(self, st: QueryState) -> None:
        """Release a dead query's cameras: its applied set drains after one
        control latency; union cameras no other live query wants go dark."""
        lat = self.sim.network.man_latency_s
        sched = self.sim.schedule
        for cam in sorted(st.requested):
            sched(lat, self._apply_query_active, st.query_id, cam, False)
        st.requested = set()
        union: Set[int] = set()
        for s in self.registry.live_states():
            union |= s.requested
        set_active = self.compiled.set_fc_active
        for cam in sorted(self._ctrl_target - union):
            sched(lat, set_active, cam, False)
        self._ctrl_target = union

    # ------------------------------------------------------------------ #
    # Control application: per-query mirrors + the event tag map          #
    # ------------------------------------------------------------------ #
    def _apply_query_active(self, qid: int, cam: int, want: bool) -> None:
        st = self.registry.states.get(qid)
        if st is None:
            return
        mask_of = self._mask_of
        if want:
            if st.dead:
                return  # in-flight activation outlived its query
            st.applied.add(cam)
            mask_of[cam] = mask_of.get(cam, 0) | st.bit
        else:
            st.applied.discard(cam)
            mask_of[cam] = mask_of.get(cam, 0) & ~st.bit

    # ------------------------------------------------------------------ #
    # TL plane: per-query spotlights, one union control delta             #
    # ------------------------------------------------------------------ #
    def _tl_tick(self) -> None:  # overrides TrackingScenario
        with span("repro.tl.tick"):
            now = self.sim.time
            dets = self._pending_detections
            masks = self._pending_masks
            self._pending_detections = []
            self._pending_masks = []
            live = self.registry.live_states()
            targets = self._query_targets(live, dets, masks, now)
            lat = self.sim.network.man_latency_s
            sched = self.sim.schedule
            union: Set[int] = set()
            for st, new_active in zip(live, targets):
                st.active_timeline.append((now, len(new_active)))
                prev = st.requested
                for cam in new_active - prev:
                    sched(lat, self._apply_query_active, st.query_id, cam, True)
                for cam in prev - new_active:
                    sched(lat, self._apply_query_active, st.query_id, cam, False)
                st.requested = new_active
                union |= new_active
            self._stats_active.append((now, len(union)))
            prev = self._ctrl_target
            set_active = self.compiled.set_fc_active
            for cam in union - prev:
                sched(lat, set_active, cam, True)
            for cam in prev - union:
                sched(lat, set_active, cam, False)
            self._ctrl_target = union
            self._drain_admission_queue()
            if now + self.cfg.tl_update_period <= self.cfg.duration_s:
                self.sim.schedule(self.cfg.tl_update_period, self._tl_tick)

    def _query_targets(
        self, live: List[QueryState], dets, masks, now: float
    ) -> List[Set[int]]:
        if self._spotlight_mode != "kernel":
            # Reference path: each query's own TL strategy, the exact solo
            # code path (what the bit-exactness harness freezes).
            return [
                st.tl.update(
                    [d for d, m in zip(dets, masks) if m & st.bit], now
                )
                for st in live
            ]
        # Fused path: contraction handled inline; every blind-spot ball is
        # computed by ONE multi-source spotlight_ball dispatch (grouped by
        # coverage so TLWBFS and TLProbabilistic queries can mix).
        targets: List[Optional[Set[int]]] = [None] * len(live)
        groups: Dict[Optional[float], List[Tuple[int, int, float]]] = {}
        for i, st in enumerate(live):
            tl = st.tl
            bit = st.bit
            positives = [
                d for d, m in zip(dets, masks) if (m & bit) and d.positive
            ]
            if positives:
                latest = max(positives, key=lambda d: d.timestamp)
                tl.last_seen_camera = latest.camera_id
                tl.last_seen_time = latest.timestamp
                tl.active = {latest.camera_id}
                targets[i] = set(tl.active)
                continue
            src = (
                tl.camera_vertices.get(tl.last_seen_camera)
                if tl.last_seen_camera is not None
                else None
            )
            radius = tl._radius_m(now)
            if src is None or math.isinf(radius):
                tl.active = set(tl.camera_vertices)
                targets[i] = set(tl.active)
                continue
            coverage = tl.coverage if isinstance(tl, TLProbabilistic) else None
            groups.setdefault(coverage, []).append((i, src, radius))
        for coverage, entries in groups.items():
            per_source = multi_source_spotlight(
                self.road,
                self.cameras.camera_vertices,
                [src for _, src, _ in entries],
                [rad for _, _, rad in entries],
                coverage=coverage,
            )
            for (i, _, _), cams in zip(entries, per_source):
                live[i].tl.active = set(cams)
                targets[i] = cams
        return targets  # type: ignore[return-value]

    def _drain_admission_queue(self) -> None:
        ctrl = self.admission
        if ctrl is None or not ctrl.queue:
            return
        reg = self.registry
        while ctrl.queue:
            qid = ctrl.queue[0]
            st = reg.get(qid)
            if st.dead:
                ctrl.queue.pop(0)
                continue
            if not ctrl.admittable(self, reg.live_count()):
                break  # FIFO head blocked: budget still degraded / cap hit
            ctrl.queue.pop(0)
            ctrl.requeued += 1
            reg.admitted += 1
            self._activate_query(st, immediate=False)

    # ------------------------------------------------------------------ #
    # Per-query accounting hooks                                          #
    # ------------------------------------------------------------------ #
    def _on_sourced(self, frames, t: float) -> None:
        if self.journal is not None:
            self.journal.append("source", t, len(frames))
        mask_of = self._mask_of
        for_mask = self.registry.for_mask
        # Aggregate per distinct mask first: N identical queries share one
        # mask value, so the charge loop runs once per mask per tick, not
        # once per (frame, query).
        counts: Dict[int, int] = {}
        for f in frames:
            m = mask_of.get(f.camera_id, 0)
            counts[m] = counts.get(m, 0) + 1
            if f.has_entity:
                for st in for_mask(m):
                    st.positives_generated += 1
        for m, c in counts.items():
            for st in for_mask(m):
                st.sourced += c

    def _on_sink_event(self, ev: Event, now: float) -> None:
        mask = ev.query_mask
        super()._on_sink_event(ev, now)
        self._pending_masks.append(mask)
        det = self._pending_detections[-1]
        if self.journal is not None:
            self.journal.append("sink", now, mask, 1.0 if det.positive else 0.0)
        h = ev.header
        u = now - h.source_arrival
        gamma = self.app.gamma
        eps_max = self.deployment.epsilon_max
        positive = det.positive
        on_time = u <= gamma
        for st in self.registry.for_mask(mask):
            if st.live:
                st.completed += 1
                st.latencies.append((now, u))
                if on_time:
                    st.on_time += 1
                else:
                    st.delayed += 1
                if positive:
                    st.positives_completed += 1
                    if on_time:
                        st.detections_on_time += 1
                    if self._quality_on:
                        st.sink_positive_pairs.append(
                            (det.camera_id, det.timestamp)
                        )
                    if st.state == "scoped":
                        self.registry.mark(st, "found", now)
                st.record_completion(
                    h.event_id, u, h.q_bar, h.xi_bar, gamma, eps_max
                )
            else:
                # In flight when its query ended: never *executed for* the
                # dead query — orphan-accounted so the books still balance.
                st.orphan_completed += 1

    def _on_pipeline_drop(self, ev: Event, point: int, epsilon: float) -> None:
        mask = ev.query_mask
        if self.journal is not None:
            self.journal.append("drop", self.sim.time, point, mask)
        if not mask:
            return
        h = ev.header
        u = self.sim.time - h.source_arrival
        for st in self.registry.for_mask(mask):
            if st.live:
                st.dropped += 1
                st.dp[point] += 1
                if point != DP_FAULT:
                    # A fault loss is not a §4.3 deadline reject: it carries
                    # no information about the query's budget, so it must not
                    # drive the per-query beta down.
                    st.record_drop(h.event_id, u, h.q_bar, h.xi_bar, epsilon)
            else:
                st.orphan_dropped += 1

    # ------------------------------------------------------------------ #
    # Fused cross-query re-ID: the tenancy and credit of the VA re-ID hook #
    # ------------------------------------------------------------------ #
    def _reid_tenancy(self, events: List[Event], idx: List[int]):  # overrides TrackingScenario
        """The live-query block, its states in bit order, and each gallery
        row's tenancy mask (pair ``(row, q)`` is evaluated only when the
        row's frame was sourced for query ``q``)."""
        block, block_states = self.registry.embedding_block()
        if not block_states:
            return None
        mask = np.zeros((len(idx), len(block_states)), dtype=bool)
        for row, i in enumerate(idx):
            m = events[i].query_mask
            for col, st in enumerate(block_states):
                if m & st.bit:
                    mask[row, col] = True
        return block, block_states, mask

    def _credit_reid(self, block_states: List[QueryState], matched) -> List[bool]:
        """Also count each query's matches, for the queries of the call
        (``block_states``, as they were at call time)."""
        hits = super()._credit_reid(block_states, matched)
        if True in hits:  # a few calls in a hundred
            per_query = np.count_nonzero(np.asarray(matched), axis=0).tolist()
            for st, n in zip(block_states, per_query):
                st.reid_matched += n
        return hits

    # ------------------------------------------------------------------ #
    # Telemetry + quality: per-query keyed rows                           #
    # ------------------------------------------------------------------ #
    def _sample_telemetry_now(self) -> None:
        super()._sample_telemetry_now()
        trace = self._trace
        for qid, st in sorted(self.registry.states.items()):
            trace.sample_keyed(f"Q:{qid}", st.telemetry_row())

    def _per_query_quality(self, st: QueryState) -> Dict[str, float]:
        """Track recall/precision over the query's live window — the same
        (camera, tick) ground-truth pairs as the global report, restricted
        to [scoped_at, ended_at]."""
        w0 = st.scoped_at if st.scoped_at is not None else math.inf
        w1 = st.ended_at if st.ended_at is not None else math.inf
        truth = {(c, t) for (c, t) in self._truth_pairs if w0 <= t <= w1}
        detected = set(st.sink_positive_pairs)
        tp = len(detected & truth)
        return {
            "truth_events": len(truth),
            "track_recall": round(tp / len(truth), 4) if truth else 1.0,
            "track_precision": round(tp / len(detected), 4) if detected else 1.0,
        }

    # ------------------------------------------------------------------ #
    # Durability: journal ticks + snapshot/restore (repro.serving.journal) #
    # ------------------------------------------------------------------ #
    _STATE_INDEX = ("submitted", "scoped", "found", "cancelled", "expired")

    def _schedule_ticks(self) -> None:  # overrides TrackingScenario
        if self._ticks_scheduled:
            return
        super()._schedule_ticks()
        j = self.journal
        if j is not None and j.snapshot_period_s > 0:
            # First snapshot one period in (t=0 state is the constructor's).
            self.sim.schedule(j.snapshot_period_s, self._journal_tick)

    def _journal_tick(self) -> None:
        j = self.journal
        j.snapshots.append(self.snapshot())
        if self.sim.time + j.snapshot_period_s <= self._horizon:
            self.sim.schedule(j.snapshot_period_s, self._journal_tick)

    def run_until(self, t: float) -> None:  # overrides TrackingScenario
        # Mark started *before* events fire so mid-run submissions take the
        # control-latency path, exactly as in an uninterrupted run().
        self._started = True
        super().run_until(t)

    def snapshot(self) -> Dict[str, float]:
        """The serving frontier as a flat ``str -> float`` dict: global
        counters, the compiled pipeline's per-task counters/budgets, every
        query's registry ledger, and the admission queue.  Bit-comparable
        between a replayed and an uninterrupted run (and npz-persistable via
        :mod:`repro.training.checkpoint`)."""
        self._settle_reid()
        snap: Dict[str, float] = {
            "time": float(self.sim.time),
            "source_events": float(self._source_events),
            "positives_generated": float(self._positives_generated),
            "positives_completed": float(self._positives_completed),
            "reid_matched": float(self._reid_matched),
        }
        snap.update(self.compiled.snapshot())
        for qid, st in sorted(self.registry.states.items()):
            p = f"q{qid}"
            try:
                state_ix = self._STATE_INDEX.index(st.state)
            except ValueError:
                state_ix = -1
            snap[f"{p}::state"] = float(state_ix)
            for k in (
                "sourced",
                "completed",
                "dropped",
                "on_time",
                "delayed",
                "orphan_completed",
                "orphan_dropped",
                "positives_generated",
                "positives_completed",
                "detections_on_time",
                "reid_matched",
                "accepts",
                "rejects",
            ):
                snap[f"{p}::{k}"] = float(getattr(st, k))
            for i in (1, 2, 3, 4):
                snap[f"{p}::dp{i}"] = float(st.dp[i])
            snap[f"{p}::beta"] = float(st.beta())
        ctrl = self.admission
        if ctrl is not None:
            snap["adm::queue_len"] = float(len(ctrl.queue))
            snap["adm::requeued"] = float(ctrl.requeued)
            for k, v in ctrl.decisions.items():
                snap[f"adm::{k}"] = float(v)
        return snap

    def restore(self, source: Any) -> "MultiQueryScenario":
        """Recover a crashed driver: replay this (freshly built) scenario to
        the snapshot's timestamp and verify the reconstructed frontier is
        bit-identical to it.

        ``source`` is a snapshot dict or a :class:`~repro.serving.journal.
        Journal` (its last snapshot is used).  The simulation is
        deterministic in (config, spec, seed), so replaying the same inputs
        reconstructs the exact pre-crash state; the bit-compare is the gate
        that proves it (``RestoreMismatch`` lists every differing key).
        After restore, ``run()`` continues to the horizon and the final
        per-query summaries equal an uninterrupted run's exactly."""
        from repro.serving.journal import RestoreMismatch, diff_snapshots

        snap = source.last_snapshot() if hasattr(source, "last_snapshot") else source
        if self.sim.time > 0.0:
            raise RuntimeError(
                "restore() replays from t=0 and needs a freshly built "
                f"scenario; this one already ran to t={self.sim.time}"
            )
        self.run_until(snap["time"])
        if self.journal is not None and self.journal.snapshots:
            # Aligned compare: the replay's own journal tick fires at the
            # *identical position in the event order* as the original's
            # (same seeds, same schedule seqs), so its latest snapshot is
            # the exact frontier the stored one captured — even when other
            # events share the snapshot's timestamp.
            mine = self.journal.snapshots[-1]
        else:
            # No journal on the replay: compare the end-of-timestamp
            # frontier (exact only when the snapshot time falls between
            # event timestamps — prefer restoring with a journal).
            mine = self.snapshot()
        diff = diff_snapshots(snap, mine)
        if diff:
            raise RestoreMismatch(
                "replayed state does not match snapshot:\n  " + "\n  ".join(diff)
            )
        return self

    # ------------------------------------------------------------------ #
    def run(self) -> MultiQueryResult:  # type: ignore[override]
        self._started = True
        self.engine_used = "interpreted"
        self.engine_fallback_reason = "engine=interpreted"
        self.engine_xfer_s = 0.0  # device->host pull wall (device backend)
        self.shards_used = 1  # mesh shards the scan actually ran on
        # Sharding totality (GRF005 extended): "" means the sharded scan
        # ran; anything else says why it didn't — never silent.  The
        # sharded path overwrites this once it decides.
        self.shard_fallback_reason = (
            "mesh-unused" if self.mesh_rules is not None else "no-mesh"
        )
        self.collective_bytes_per_tick = 0.0
        if getattr(self.cfg, "engine", "interpreted") == "megastep":
            from repro.core.megastep import try_run_megastep

            fused = try_run_megastep(self)
            if fused is not None:
                return fused
            # None: either ineligible (interpreted fallback) or the drops-on
            # backend primed its tick chain — both continue below.
        base = super().run()
        per_query: Dict[int, ScenarioResult] = {}
        for qid, st in sorted(self.registry.states.items()):
            quality = self._per_query_quality(st) if self._quality_on else None
            per_query[qid] = ScenarioResult(
                config=self.cfg,
                active_timeline=list(st.active_timeline),
                latencies=list(st.latencies),
                on_time=st.on_time,
                delayed=st.delayed,
                source_events=st.sourced,
                dropped=st.dropped,
                drops_by_task={
                    **{f"dp{i}": st.dp[i] for i in (1, 2, 3) if st.dp[i]},
                    **({"dp_fault": st.dp[4]} if st.dp[4] else {}),
                },
                batch_sizes={},
                positives_generated=st.positives_generated,
                positives_completed=st.positives_completed,
                positives_dropped=st.positives_generated - st.positives_completed,
                detections_on_time=st.detections_on_time,
                reid_matched=st.reid_matched,
                query_pushes=0,
                trace=None,
                quality=quality,
            )
        return MultiQueryResult(
            result=base,
            per_query=per_query,
            registry=self.registry,
            admission=self.admission,
            states={qid: st.state for qid, st in sorted(self.registry.states.items())},
        )

    def publish_metrics(  # type: ignore[override]
        self, registry, res: MultiQueryResult
    ) -> None:
        """Publish global + per-query telemetry into an obs-plane registry.

        Thin delegation to :func:`repro.obs.collect_query_result` (lazy
        import so the query layer never depends on the obs package at
        module load).
        """
        from repro.obs import collect_query_result

        collect_query_result(registry, self, res)


# --------------------------------------------------------------------- #
# Per-query-serial baseline                                              #
# --------------------------------------------------------------------- #
def _solo_scenario(config: ScenarioConfig, spec: QuerySpec) -> TrackingScenario:
    """One independent single-query scenario equivalent to ``spec`` —
    including the overrides ``ScenarioConfig`` cannot express (``coverage``,
    ``last_seen_camera`` warm start, ``make_tl``), which are applied by
    building the preset app's TL exactly the way ``_activate_query`` does."""
    cfg = spec.solo_config(config)
    if (
        spec.coverage is None
        and spec.last_seen_camera is None
        and spec.make_tl is None
    ):
        return TrackingScenario(cfg)

    def app_factory(world, cameras):
        from dataclasses import replace

        app = cfg.to_app(world, cameras)
        if spec.make_tl is not None:
            tl = spec.make_tl(world, cameras)
        else:
            tl = cfg.make_tl(world.road, cameras.camera_vertices)
        if spec.coverage is not None and hasattr(tl, "coverage"):
            tl.coverage = float(spec.coverage)
        if spec.last_seen_camera is not None:
            tl.last_seen_camera = spec.last_seen_camera
            tl.last_seen_time = 0.0
            tl.active = tl.spotlight(0.0)
        return replace(app, tl=tl)

    return TrackingScenario(cfg, app=app_factory)


def run_queries_serial(
    config: ScenarioConfig, queries: Union[int, Sequence[QuerySpec]]
) -> Tuple[List[ScenarioResult], float]:
    """The baseline the fused plane is measured (and bit-compared) against:
    one independent single-query ``TrackingScenario`` per spec, run
    sequentially (worlds shared through the process-wide warm cache).
    ``submit_at``/``cancel_at``/``ttl_s`` have no solo equivalent — each
    baseline runs its query for the whole horizon.  Returns the per-query
    results and the total wall time."""
    specs = normalize_queries(queries)
    t0 = time.perf_counter()
    results = [_solo_scenario(config, spec).run() for spec in specs]
    return results, time.perf_counter() - t0
