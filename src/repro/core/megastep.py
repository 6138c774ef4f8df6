"""Mega-step driver: eligibility, the host-precomputed plan, and result
assembly for the fused tick engine (`repro.kernels.megastep`).

``ScenarioConfig.engine = "megastep"`` lowers eligible multi-query runs to
one engine invocation instead of one scheduler event per pipeline hop:

* **device** — base/bfs/wbfs per-query TLs, drops off, at most 64 queries:
  the whole run executes as one jax ``lax.scan`` over ticks
  (`kernels.megastep.ops`), with camera activity masks, query tag bits,
  the spotlight distance/hop planes and the radius tables resident on
  device; only compact per-(tick, lane, slot) summary rows come back.
* **host** — probabilistic TLs, kernel spotlight mode, or > 64 queries:
  the same chain state machine in numpy (`kernels.megastep.ref`) with the
  real TL objects doing the spotlight step.
* **des** (drops on) — the per-event drop/budget/probe machinery is
  inherently sequential (reject/accept signals mutate budgets between
  events), so the mega-step keeps the event-driven task graph and replaces
  the source plane with its plan-driven tick driver (precomputed tick
  chain + visibility table).

Everything else — faults, dynamism, non-static xi, admission control,
journaling, staged query lifecycles — falls back to the interpreted
pipeline, which remains the reference.  The engine is gated on
bit-exactness: per-query and global summaries must equal the interpreted
``MultiQueryScenario`` exactly (see ``tests/test_megastep.py``).
"""

from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..kernels.megastep import ref as _ref
from .tracking import Detection, TLBase, TLBFS, TLWBFS

__all__ = ["MegastepPlan", "megastep_backend", "try_run_megastep"]


# --------------------------------------------------------------------- #
# Eligibility                                                            #
# --------------------------------------------------------------------- #
def megastep_backend(scn) -> Tuple[Optional[str], str]:
    """Classify a ``MultiQueryScenario`` for the mega-step engine.

    Returns ``(backend, reason)`` where backend is ``"device"``, ``"host"``,
    ``"des"`` or ``None`` (fall back to the interpreted pipeline; ``reason``
    says why).
    """
    cfg = scn.cfg
    if getattr(cfg, "engine", "interpreted") != "megastep":
        return None, "engine!=megastep"
    if cfg.dynamism is not None:
        return None, "dynamism"
    if getattr(scn.sim, "faults", None) is not None:
        return None, "faults"
    if scn.journal is not None:
        return None, "journal"
    if scn.admission is not None:
        return None, "admission"
    if cfg.embed_dim:
        return None, "embed_dim"
    if scn.sim.time != 0.0 or scn._ticks_scheduled:
        return None, "already-running"
    states = scn.registry.states
    if not states:
        return None, "no-queries"
    for st in states.values():
        spec = st.spec
        if (
            spec.submit_at > 0.0
            or spec.cancel_at is not None
            or spec.ttl_s is not None
            or spec.make_tl is not None
            or spec.embedding_seed is not None
        ):
            return None, "query-lifecycle"
        if not st.live or st.state != "scoped":
            return None, "query-state"
        tl = st.tl
        if tl.last_seen_time != 0.0 or tl.last_seen_camera is None:
            return None, "tl-seed"
    if cfg.drops_enabled:
        # The signal machinery is sequential by design; keep the event DAG
        # and drive it from the plan (host tick driver).
        return "des", ""
    compiled = scn.compiled
    if not compiled.fuse_fc:
        # fuse_fc already encodes: pass-through FC, static transit + xi,
        # fps > 0 and a frame period longer than xi_fc(1).
        return None, "no-fuse-fc"
    L = len(compiled.va_tasks)
    if len(compiled.cr_tasks) != L or L == 0:
        return None, "va/cr-instances"
    if cfg.batching == "static":
        if cfg.static_batch != 1:
            return None, "static-batch>1"
    elif cfg.batching != "dynamic":
        # Budget-less dynamic batching is pinned to b=1 (bootstrap regime),
        # i.e. streaming — anything else keeps the interpreted pipeline.
        return None, f"batching={cfg.batching}"
    if cfg.tl_update_period != 1.0 / cfg.fps:
        return None, "tl-period!=frame-period"
    net = getattr(scn.sim, "network", None)
    lat = getattr(net, "man_latency_s", None)
    if lat is None or not (0.0 < lat < cfg.tl_update_period):
        return None, "control-latency"
    if not (cfg.duration_s >= 0.0 and math.isfinite(cfg.duration_s)):
        return None, "duration"
    for i in range(L):
        va, cr = compiled.va_tasks[i], compiled.cr_tasks[i]
        if va.node != cr.node:
            return None, "va/cr-colocation"
    if scn._spotlight_mode == "kernel":
        return "host", ""
    for st in states.values():
        tl = st.tl
        if type(tl) not in (TLBase, TLBFS, TLWBFS):
            return "host", ""
        if not (math.isfinite(tl.entity_speed) and math.isfinite(tl.min_radius_m)):
            return "host", ""
    if len(states) > 64:
        return "host", ""
    return "device", ""


# --------------------------------------------------------------------- #
# Plan: everything the engine needs, precomputed once on the host        #
# --------------------------------------------------------------------- #
@dataclass
class MegastepPlan:
    ftimes: np.ndarray          # (T,) f64 frame/TL tick chain
    vis: np.ndarray             # (T, C) bool entity visibility
    lane_of: np.ndarray         # (C,) int64 cam -> VA/CR lane
    num_lanes: int
    num_cameras: int
    xi_fc: float
    xi_va: float
    xi_cr: float
    xi_bar: float               # (xi_fc + xi_va) + xi_cr, header float order
    d_fv: float                 # fused FC -> VA transit
    d_vc: float                 # VA -> CR (same-host ipc)
    d_cu: float                 # CR -> sink
    uniforms: np.ndarray        # (dmax,) shared CR verdict stream
    p_tp: float
    gamma: float
    eps_max: float
    duration: float
    horizon: float
    # Table-TL planes (device backend; None for the host-object backend)
    modes: Optional[np.ndarray] = None        # (N,) 0 base / 1 bfs / 2 wbfs
    rgroup: Optional[np.ndarray] = None       # (N,) radius-table group
    r_tabs: List[np.ndarray] = field(default_factory=list)   # [(T, T) f64]
    h_tabs: List[np.ndarray] = field(default_factory=list)   # [(T, T) i64]
    cand_of_cam: Optional[np.ndarray] = None  # (C,) i64, -1 = not candidate
    dist_plane: Optional[np.ndarray] = None   # (n_cand, C) f64
    hop_plane: Optional[np.ndarray] = None    # (n_cand, C) i64
    seed_ls_cam: Optional[np.ndarray] = None  # (N,) i64


def _dijkstra_row(adjacency, source: int, n: int) -> np.ndarray:
    """Full Dijkstra with the exact float semantics of
    ``RoadNetwork.weighted_ball`` (heap pops, ``nd = d + w``, strict ``<``),
    so plane distances equal the ball's distances bit-for-bit."""
    dist = np.full(n, np.inf)
    dist[source] = 0.0
    heap: List[Tuple[float, int]] = [(0.0, source)]
    pop, push = heapq.heappop, heapq.heappush
    while heap:
        d, u = pop(heap)
        if d > dist[u]:
            continue
        for v, w in adjacency[u]:
            nd = d + w
            if nd < dist[v]:
                dist[v] = nd
                push(heap, (nd, v))
    return dist


def _bfs_row(adjacency, source: int, n: int) -> np.ndarray:
    hops = np.full(n, np.iinfo(np.int64).max, dtype=np.int64)
    hops[source] = 0
    frontier = [source]
    h = 0
    while frontier:
        h += 1
        nxt: List[int] = []
        for u in frontier:
            for v, _ in adjacency[u]:
                if hops[v] > h:
                    hops[v] = h
                    nxt.append(v)
        frontier = nxt
    return hops


def build_plan(scn, backend: str) -> MegastepPlan:
    cfg = scn.cfg
    compiled = scn.compiled
    sim = scn.sim
    C = scn.cameras.num_cameras
    L = len(compiled.va_tasks)

    # Frame tick chain: t=0, then t += 1/fps while the next tick still fits
    # in the generation window — the scheduler's accumulated-float times.
    dt = 1.0 / cfg.fps
    ftimes = [0.0]
    t = 0.0
    while t + dt <= cfg.duration_s:
        t = t + dt
        ftimes.append(t)
    ftimes_arr = np.asarray(ftimes, dtype=np.float64)
    T = len(ftimes)

    all_ids = np.arange(C, dtype=np.int64)
    vis = np.empty((T, C), dtype=bool)
    for k in range(T):
        vis[k] = scn.cameras.visible_batch(all_ids, float(ftimes_arr[k]))

    lane_of = all_ids % L

    if backend == "des":
        # The drops-on tick driver keeps the real task DAG: it only needs
        # the tick chain and the visibility table.
        xi_fc = xi_va = xi_cr = d_fv = d_vc = d_cu = 0.0
        uniforms = np.empty(0)
    else:
        va0, cr0 = compiled.va_tasks[0], compiled.cr_tasks[0]
        d_fv = compiled.fc_transit
        d_vc = sim.transit_delay(va0.node, cr0.node, va0.output_event_bytes)
        d_cu = sim.transit_delay(cr0.node, scn.sink.node, cr0.output_event_bytes)
        xi_fc = compiled.fc_xi1
        xi_va = va0.xi(1)
        xi_cr = cr0.xi(1)
        visc = vis.sum(axis=0, dtype=np.int64)
        lane_draws = np.bincount(lane_of, weights=visc, minlength=L)
        dmax = int(lane_draws.max()) if L else 0
        uniforms = np.random.default_rng(cfg.seed + 101).uniform(size=dmax)

    plan = MegastepPlan(
        ftimes=ftimes_arr,
        vis=vis,
        lane_of=lane_of,
        num_lanes=L,
        num_cameras=C,
        xi_fc=xi_fc,
        xi_va=xi_va,
        xi_cr=xi_cr,
        xi_bar=(xi_fc + xi_va) + xi_cr,
        d_fv=d_fv,
        d_vc=d_vc,
        d_cu=d_cu,
        uniforms=uniforms,
        p_tp=cfg.p_true_positive,
        gamma=scn.app.gamma,
        eps_max=scn.deployment.epsilon_max,
        duration=cfg.duration_s,
        horizon=scn._horizon,
    )
    if backend != "device":
        return plan

    # ---- table-TL planes ------------------------------------------------ #
    live = scn.registry.live_states()
    N = len(live)
    cam_vertex = np.fromiter(
        (scn.cameras.camera_vertices[int(c)] for c in all_ids),
        dtype=np.int64,
        count=C,
    )
    modes = np.zeros(N, dtype=np.int8)
    seed_ls = np.zeros(N, dtype=np.int64)
    group_key: Dict[Tuple[float, float, float], int] = {}
    rgroup = np.zeros(N, dtype=np.int64)
    r_tabs: List[np.ndarray] = []
    h_tabs: List[np.ndarray] = []
    elapsed = np.maximum(ftimes_arr[None, :] - ftimes_arr[:, None], 0.0)
    for i, st in enumerate(live):
        tl = st.tl
        modes[i] = {TLBase: 0, TLBFS: 1, TLWBFS: 2}[type(tl)]
        seed_ls[i] = int(tl.last_seen_camera)
        fe = getattr(tl, "fixed_edge_length_m", 84.5)
        key = (float(tl.min_radius_m), float(tl.entity_speed), float(fe))
        g = group_key.get(key)
        if g is None:
            g = len(r_tabs)
            group_key[key] = g
            r = tl.min_radius_m + tl.entity_speed * elapsed
            r_tabs.append(r)
            h_tabs.append(np.ceil(r / fe).astype(np.int64))
        rgroup[i] = g

    ever_vis = np.nonzero(vis.any(axis=0))[0]
    cand_cams = set(int(c) for c in ever_vis) | set(int(c) for c in seed_ls)
    cand_vertices: List[int] = []
    vert_row: Dict[int, int] = {}
    for c in sorted(cand_cams):
        v = int(cam_vertex[c])
        if v not in vert_row:
            vert_row[v] = len(cand_vertices)
            cand_vertices.append(v)
    cand_of_cam = np.full(C, -1, dtype=np.int64)
    for c in sorted(cand_cams):
        cand_of_cam[c] = vert_row[int(cam_vertex[c])]

    adjacency = scn.road.adjacency
    V = scn.road.num_vertices
    n_cand = len(cand_vertices)
    dist_plane = np.empty((n_cand, C), dtype=np.float64)
    hop_plane = np.empty((n_cand, C), dtype=np.int64)
    need_hops = bool((modes == 1).any())
    need_dist = bool((modes == 2).any())
    for r_i, v in enumerate(cand_vertices):
        if need_dist or True:
            dist_plane[r_i] = _dijkstra_row(adjacency, v, V)[cam_vertex]
        if need_hops:
            hop_plane[r_i] = _bfs_row(adjacency, v, V)[cam_vertex]
    if not need_hops:
        hop_plane[:] = 0

    plan.modes = modes
    plan.rgroup = rgroup
    plan.r_tabs = r_tabs
    plan.h_tabs = h_tabs
    plan.cand_of_cam = cand_of_cam
    plan.dist_plane = dist_plane
    plan.hop_plane = hop_plane
    plan.seed_ls_cam = seed_ls
    return plan


# --------------------------------------------------------------------- #
# Result assembly (drops-off backends)                                   #
# --------------------------------------------------------------------- #
def _seed_applied(live, C: int) -> np.ndarray:
    req = np.zeros((len(live), C), dtype=bool)
    for i, st in enumerate(live):
        if st.requested:
            req[i, np.fromiter(st.requested, dtype=np.int64, count=len(st.requested))] = True
    return req


def _make_object_tl(scn, plan, live):
    """TL callback using the real per-query TL objects (host backend) —
    exactly ``MultiQueryScenario._query_targets``, including kernel
    spotlight mode."""
    ftimes = plan.ftimes
    C = plan.num_cameras
    bits = [st.bit for st in live]

    def tl_step(k: int, dets: List[_ref.SinkRow]) -> np.ndarray:
        now = float(ftimes[k])
        det_objs = [
            Detection(camera_id=r.cam, positive=r.positive, timestamp=float(ftimes[r.tick]))
            for r in dets
        ]
        masks = [
            int(sum(b for b, m in zip(bits, r.mask) if m)) for r in dets
        ]
        targets = scn._query_targets(live, det_objs, masks, now)
        req = np.zeros((len(live), C), dtype=bool)
        for i, (st, cams) in enumerate(zip(live, targets)):
            st.requested = set(cams)
            if cams:
                req[i, np.fromiter(cams, dtype=np.int64, count=len(cams))] = True
        return req

    return tl_step


def _finalize(scn, plan: MegastepPlan, out: _ref.ChainOutput, live):
    """Build the MultiQueryResult from the engine's summary rows, writing
    the same per-query registry books the interpreted hooks fill."""
    from ..query.scenario import MultiQueryResult
    from ..sim.scenario import ScenarioResult

    reg = scn.registry
    gamma = plan.gamma
    eps_max = plan.eps_max
    horizon = plan.horizon
    xi_bar = plan.xi_bar

    for k, counts, union_count in out.tl_counts:
        now = float(plan.ftimes[k])
        for st, c in zip(live, counts):
            st.active_timeline.append((now, int(c)))
        scn._stats_active.append((now, union_count))
    for i, st in enumerate(live):
        st.sourced = int(out.sourced[i])
        st.positives_generated = int(out.query_positives[i])
    scn._source_events = out.source_events
    scn._positives_generated = out.positives_generated

    latencies: List[Tuple[float, float]] = []
    on_time = delayed = 0
    for j, r in enumerate(out.rows):
        if r.a_uv > horizon:
            continue  # still in flight when the drain window closed
        u = r.u
        latencies.append((r.a_uv, u))
        ok = u <= gamma
        if ok:
            on_time += 1
        else:
            delayed += 1
        if r.positive:
            scn._positives_completed += 1
            if ok:
                scn._detections_on_time += 1
        for i in np.nonzero(r.mask)[0]:
            st = live[i]
            st.completed += 1
            st.latencies.append((r.a_uv, u))
            if ok:
                st.on_time += 1
            else:
                st.delayed += 1
            if r.positive:
                st.positives_completed += 1
                if ok:
                    st.detections_on_time += 1
                if st.state == "scoped":
                    reg.mark(st, "found", r.a_uv)
            st.record_completion(j, u, r.q_bar, xi_bar, gamma, eps_max)

    cfg = scn.cfg
    base = ScenarioResult(
        config=cfg,
        active_timeline=scn._stats_active,
        latencies=latencies,
        on_time=on_time,
        delayed=delayed,
        source_events=scn._source_events,
        dropped=0,
        drops_by_task={},
        batch_sizes={
            "VA": [1] * int(out.va_exec_counts.sum()),
            "CR": [1] * int(out.cr_exec_counts.sum()),
        },
        positives_generated=scn._positives_generated,
        positives_completed=scn._positives_completed,
        positives_dropped=scn._positives_generated - scn._positives_completed,
        detections_on_time=scn._detections_on_time,
        reid_matched=0,
        query_pushes=scn.compiled.query_pushes,
        trace=None,
        quality=None,
    )
    per_query: Dict[int, ScenarioResult] = {}
    for qid, st in sorted(reg.states.items()):
        per_query[qid] = ScenarioResult(
            config=cfg,
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
            quality=None,
        )
    return MultiQueryResult(
        result=base,
        per_query=per_query,
        registry=reg,
        admission=scn.admission,
        states={qid: st.state for qid, st in sorted(reg.states.items())},
    )


# --------------------------------------------------------------------- #
# Drops-on: plan-driven source plane over the event DAG                  #
# --------------------------------------------------------------------- #
def _prime_des(scn, plan: MegastepPlan) -> None:
    """Install the mega-step source plane: the precomputed tick chain and
    visibility table replace the per-tick position interpolation + FOV
    test, while the real tasks keep the drop/budget/probe semantics.  The
    caller then proceeds with the normal run loop."""
    from .events import Event, new_event_id, source_header
    from ..sim.cameras import Frame

    cfg = scn.cfg
    compiled = scn.compiled
    sim = scn.sim
    vis = plan.vis
    dt = 1.0 / cfg.fps
    tick_idx = [0]

    def frame_tick() -> None:
        t = sim.time
        k = tick_idx[0]
        tick_idx[0] += 1
        fc_active = compiled.fc_active
        if fc_active:
            ids = np.fromiter(fc_active, dtype=np.int64, count=len(fc_active))
            ids.sort()
            vis_k = vis[k]
            mask_of = scn._mask_of
            frames = [
                Frame(camera_id=int(c), timestamp=t, has_entity=bool(vis_k[c]))
                for c in ids
                if mask_of.get(int(c), 0)
            ]
            n_pos = 0
            fc_tasks = compiled.fc_tasks
            make_fc = compiled.make_fc
            for frame in frames:
                if frame.has_entity:
                    n_pos += 1
                cam = frame.camera_id
                fc = fc_tasks.get(cam)
                if fc is None:
                    fc = make_fc(cam)
                header = source_header(new_event_id(), t)
                ev = Event(header=header, key=cam, value=frame)
                ev.query_mask = mask_of[cam]
                fc.on_arrival(ev)
            scn._positives_generated += n_pos
            scn._source_events += len(frames)
            if scn._source_hook is not None:
                scn._source_hook(frames, t)
        if t + dt <= cfg.duration_s:
            sim.schedule(dt, frame_tick)

    scn._ticks_scheduled = True
    sim.schedule(0.0, frame_tick)
    sim.schedule(cfg.tl_update_period, scn._tl_tick)


# --------------------------------------------------------------------- #
# Entry point                                                            #
# --------------------------------------------------------------------- #
def try_run_megastep(scn):
    """Run the mega-step engine for ``scn`` if it is eligible.

    Returns a finished ``MultiQueryResult`` (drops-off device/host
    backends), or ``None`` — in which case the caller continues with the
    interpreted run loop (either as a plain fallback, or with the plan's
    source plane already primed for the drops-on backend)."""
    backend, reason = megastep_backend(scn)
    if backend is None:
        scn.engine_used = "interpreted"
        # Engine contract (verified by repro.analysis.graphcheck GRF005):
        # a requested-but-skipped megastep is never silent — every fallback
        # records why, even if a future classifier branch forgets to.
        scn.engine_fallback_reason = reason or "unclassified"
        return None
    live = scn.registry.live_states()
    plan = build_plan(scn, backend)
    if backend == "des":
        _prime_des(scn, plan)
        scn.engine_used = "megastep-des"
        scn.engine_fallback_reason = ""
        return None
    seed = _seed_applied(live, plan.num_cameras)
    fallback = ""
    if backend == "device":
        out, fallback = _run_device(scn, plan, seed)
        if out is None:
            backend = "host"
    if backend == "host":
        if scn._spotlight_mode == "kernel" or any(
            type(st.tl) not in (TLBase, TLBFS, TLWBFS) for st in live
        ):
            tl_step = _make_object_tl(scn, plan, live)
        elif plan.modes is not None:
            tl_step = _ref.make_table_tl(plan)
        else:
            tl_step = _make_object_tl(scn, plan, live)
        out = _ref.run_chain(plan, tl_step, seed)
        scn.engine_used = "megastep-host"
    else:
        scn.engine_used = "megastep-device"
    # A device run handed to the host mirror says why (x64-emulated,
    # device-capacity, device-error: ...); a run classified host has none.
    scn.engine_fallback_reason = fallback
    if out.final_req is not None:
        # Leave the registry's requested sets at the last TL tick's targets
        # (the object-TL callback already does; the table/device paths
        # report them through the chain output).
        for i, st in enumerate(live):
            st.requested = {int(c) for c in np.nonzero(out.final_req[i])[0]}
    res = _finalize(scn, plan, out, live)
    _sync_control_mirrors(scn, live)
    return res


def _sync_control_mirrors(scn, live) -> None:
    """Leave the scenario's control mirrors in their end-of-run state so
    post-run inspection matches the interpreted pipeline."""
    union: set = set()
    mask_of: Dict[int, int] = {}
    for st in live:
        st.applied = set(st.requested)
        union |= st.requested
        for cam in st.requested:
            mask_of[cam] = mask_of.get(cam, 0) | st.bit
    scn._ctrl_target = union
    scn._mask_of = mask_of
    scn.compiled.fc_active.clear()
    scn.compiled.fc_active |= union


def _run_device(scn, plan: MegastepPlan, seed_applied: np.ndarray):
    """Device scan backend; returns ``(ChainOutput, "")``, or ``(None,
    reason)`` when the run must go to the host mirror.

    With a mesh handle (``MultiQueryScenario(..., mesh=...)`` /
    ``distributed.camera_mesh()``) the scan runs camera-sharded via
    ``kernels.megastep.sharded``; any sharded-path refusal (single visible
    device, no ``cameras`` axis, non-dividing bucket) is recorded in
    ``scn.shard_fallback_reason`` — the GRF005 totality contract extended
    to sharding — and the run continues bit-identically on the unsharded
    single-shard path."""
    from ..kernels.megastep import ops as _ops

    rules = getattr(scn, "mesh_rules", None)
    if rules is not None:
        from ..kernels.megastep import sharded as _sharded

        out = _sharded.run_chain_sharded(plan, seed_applied, rules)
        if out is not None:
            scn.engine_xfer_s = _sharded.last_xfer_seconds()
            scn.shards_used = _sharded.last_shards()
            scn.collective_bytes_per_tick = (
                _sharded.last_collective_bytes_per_tick()
            )
            scn.shard_fallback_reason = ""
            chunk_walls = _sharded.last_chunk_seconds()
            scn.megastep_chunk_s = sum(chunk_walls)
            scn.megastep_chunks = len(chunk_walls)
            return out, ""
        scn.shard_fallback_reason = _sharded.last_error() or "unclassified"
    out = _ops.run_chain_device(plan, seed_applied)
    if out is None:
        return None, _ops.last_fallback_reason() or "unclassified"
    scn.engine_xfer_s = _ops.last_xfer_seconds()
    chunk_walls = _ops.last_chunk_seconds()
    scn.megastep_chunk_s = sum(chunk_walls)
    scn.megastep_chunks = len(chunk_walls)
    return out, ""
