"""Numpy reference for the fused mega-step tick engine.

Replays the drops-off streaming pipeline (fused FC sourcing -> VA
pass-through -> CR verdict -> sink) as a per-lane busy-chain state machine
over precomputed tick tables, in plain python/numpy floats.  Every float
expression mirrors the discrete-event code path it replaces:

* fused streaming exec:   ``end = arrival + xi`` (``Task.on_arrival``)
* first queued exec:      ``start = A + (busy_until - A)`` — the drain
  callback is scheduled with a *relative* delay, so the anchor is the
  arrival of the first queued event of the busy period
  (``Task.on_arrival`` -> ``_drain_fused``)
* subsequent queued:      ``start = busy_until`` (``_finish_and_continue``
  pops at the previous exec's end)
* transits: arrival = exec_end + delay, one float add per hop, identical
  for the fused (``schedule_at(depart_at + delay)``) and queued
  (``schedule(delay)`` at exec end) paths.

The TL update is a callback so two backends share the chain: the table
update in :func:`make_table_tl` (base/bfs/wbfs via precomputed radius/hop
tables and per-candidate distance planes — what `ops.py` runs on device)
and the real-TL-object update the driver supplies for probabilistic /
kernel-spotlight configs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

__all__ = ["SinkRow", "ChainOutput", "QueueLineage", "run_chain", "make_table_tl",
           "sink_sort_key"]


@dataclass
class SinkRow:
    """One event's end-to-end record: everything the sink, the TL and the
    result assembly need (a compact per-tick summary row)."""

    __slots__ = (
        "a_uv", "tick", "grank", "slot", "lane", "cam", "positive",
        "u", "q_bar", "va_fused", "va_end", "cr_arr", "cr_fused", "cr_end",
        "mask", "order",
    )
    a_uv: float      # sink arrival time
    tick: int        # source frame tick index
    grank: int       # VA delivery-group rank at the source tick (tie order)
    slot: int        # slot within the lane at the source tick
    lane: int
    cam: int
    positive: bool
    u: float         # end-to-end latency (a_uv - tick time)
    q_bar: float     # accumulated queuing (VA + CR stages)
    va_fused: bool
    va_end: float
    cr_arr: float
    cr_fused: bool
    cr_end: float
    mask: np.ndarray  # (N,) bool: per-query tag bits at source time
    order: tuple      # heap order among equal a_uv (see sink_sort_key)


def sink_sort_key(r: SinkRow) -> Tuple[float, tuple]:
    """Sink processing order: the scheduler's heap order (time, seq).

    Among equal arrival times seq follows the order in which the arrivals
    were scheduled, which ``SinkRow.order`` replays (:class:`QueueLineage`
    builds it).  A fused exec schedules its downstream arrival while its
    own arrival is processed; a queued one at its exec end, later, so
    fused rows go first.  A queued exec's finish callback was scheduled by
    its predecessor's, back to the drain armed by the first queued arrival
    of the busy period: between two queued rows ending together, the
    deeper chain reaches back past the other's drain and goes first, and
    at equal depth the earlier drain does.  Each arrival's own order is
    the same comparison one stage up (CR arrivals carry their VA
    scheduling), ending at the fused FC delivery: source tick, VA
    delivery group (rank of each lane's first active camera), slot."""
    return (r.a_uv, r.order)


class QueueLineage:
    """Heap-order lineage of one task instance's busy chain: the same
    fused / first-queued / queued states as :class:`_LaneChain`, tracking
    how many queued execs deep the current one is and which arrival armed
    the drain that started the queue."""

    __slots__ = ("depth", "root")

    def __init__(self) -> None:
        self.depth = -1   # -1: no queue armed since the last fused exec
        self.root: tuple = ()

    def key(self, fused: bool, arrival_order: tuple) -> tuple:
        """Order of the downstream arrival this exec schedules, given the
        order of its own arrival."""
        if fused:
            self.depth = -1
            return (0, arrival_order)
        if self.depth < 0:
            self.root = arrival_order
        self.depth += 1
        return (1, -self.depth, self.root)


def sink_order(va: QueueLineage, cr: QueueLineage, tick: int, grank: int,
               slot: int, va_fused: bool, cr_fused: bool, cr_arr: float) -> tuple:
    """``SinkRow.order`` of the next exec of one lane (rows of a lane must
    be fed in chain order: tick, then slot)."""
    cr_arrival = (cr_arr, va.key(va_fused, (tick, grank, slot)))
    return cr.key(cr_fused, cr_arrival)


@dataclass
class ChainOutput:
    rows: List[SinkRow]                    # all sink rows, final sink order
    source_events: int
    positives_generated: int
    sourced: np.ndarray                    # (N,) per-query sourced frames
    query_positives: np.ndarray            # (N,) per-query positives generated
    tl_counts: List[Tuple[int, np.ndarray, int]]  # (tick, (N,) active, union)
    va_exec_counts: np.ndarray             # (L,) execs counted before horizon
    cr_exec_counts: np.ndarray             # (L,)
    final_req: Optional[np.ndarray] = None  # (N, C) last requested matrix


class _LaneChain:
    """The fused-streaming busy chain of one task instance (VA-i / CR-i)."""

    __slots__ = ("b", "armed")

    def __init__(self) -> None:
        self.b = -np.inf   # busy_until after the last scheduled exec
        self.armed = False  # a drain was armed for the current busy period

    def step(self, arrival: float, xi: float) -> Tuple[float, float, bool]:
        """Process one arrival; returns (exec_end, q, fused)."""
        b = self.b
        if arrival >= b:
            end = arrival + xi
            self.b = end
            self.armed = False
            return end, 0.0, True
        if not self.armed:
            # First queued event of the busy period: the drain fires at
            # now + (busy_until - now) — up to 1 ulp from busy_until.
            start = arrival + (b - arrival)
            self.armed = True
        else:
            start = b
        end = start + xi
        self.b = end
        return end, start - arrival, False


def run_chain(
    plan,
    tl_step: Callable[[int, List[SinkRow]], np.ndarray],
    seed_applied: np.ndarray,
) -> ChainOutput:
    """Run the whole drops-off pipeline over every tick of ``plan``.

    ``plan`` is duck-typed (see ``repro.core.megastep.MegastepPlan``):
    ``ftimes (T,)``, ``vis (T, C) bool``, ``lane_of (C,) int``,
    ``num_lanes``, ``xi_fc/xi_va/xi_cr``, ``d_fv/d_vc/d_cu``,
    ``uniforms (dmax,)``, ``p_tp``, ``horizon``.

    ``tl_step(k, dets)`` consumes the detections that arrived strictly
    before tick ``k``'s time (already in sink order) and returns the
    ``(N, C)`` bool requested matrix — which becomes the *applied* matrix
    for tick ``k``'s sourcing onwards (control latency < tick period).
    ``seed_applied`` is the t=0 matrix (pre-run activation is immediate).
    """
    ftimes = plan.ftimes
    vis = plan.vis
    lane_of = plan.lane_of
    L = plan.num_lanes
    xi_fc, xi_va, xi_cr = plan.xi_fc, plan.xi_va, plan.xi_cr
    d_fv, d_vc, d_cu = plan.d_fv, plan.d_vc, plan.d_cu
    uniforms = plan.uniforms
    p_tp = plan.p_tp
    horizon = plan.horizon
    T = len(ftimes)

    va = [_LaneChain() for _ in range(L)]
    cr = [_LaneChain() for _ in range(L)]
    va_line = [QueueLineage() for _ in range(L)]
    cr_line = [QueueLineage() for _ in range(L)]
    draws = [0] * L
    applied = np.ascontiguousarray(seed_applied, dtype=bool)
    N = applied.shape[0]

    pending: List[SinkRow] = []
    rows: List[SinkRow] = []
    sourced = np.zeros(N, dtype=np.int64)
    query_pos = np.zeros(N, dtype=np.int64)
    g_source = 0
    g_pos = 0
    tl_counts: List[Tuple[int, np.ndarray, int]] = []

    for k in range(T):
        now = float(ftimes[k])
        if k >= 1:
            # TL tick fires before the frame tick at the shared time and
            # consumes every detection that arrived strictly before it.
            take = [r for r in pending if r.a_uv < now]
            if take:
                pending = [r for r in pending if not (r.a_uv < now)]
                take.sort(key=sink_sort_key)
            new_req = tl_step(k, take)
            tl_counts.append(
                (k, new_req.sum(axis=1, dtype=np.int64), int(new_req.any(axis=0).sum()))
            )
        else:
            new_req = applied

        # Sourcing uses the PREVIOUS tick's targets: the TL tick's control
        # deltas land one control latency later, after the same-time frame
        # tick (latency < tick period, checked by eligibility).
        union = applied.any(axis=0)
        cams = np.nonzero(union)[0]
        if cams.size == 0:
            applied = new_req
            continue
        sourced += applied.sum(axis=1, dtype=np.int64)
        vis_k = vis[k]
        query_pos += (applied & vis_k).sum(axis=1, dtype=np.int64)
        g_source += int(cams.size)
        g_pos += int(vis_k[cams].sum())

        # Fused FC: every sourced frame departs at t + xi_fc and arrives at
        # its VA (one grouped delivery per lane) at depart + transit.
        t_arr = (now + xi_fc) + d_fv
        lane_order: List[int] = []
        lane_slots: dict = {}
        for c in cams:
            l = int(lane_of[c])
            g = lane_slots.get(l)
            if g is None:
                lane_slots[l] = [int(c)]
                lane_order.append(l)
            else:
                g.append(int(c))
        for grank, l in enumerate(lane_order):
            va_l, cr_l = va[l], cr[l]
            for slot, c in enumerate(lane_slots[l]):
                va_end, q_va, va_fused = va_l.step(t_arr, xi_va)
                cr_arr = va_end + d_vc
                cr_end, q_cr, cr_fused = cr_l.step(cr_arr, xi_cr)
                has = bool(vis_k[c])
                if has:
                    positive = float(uniforms[draws[l]]) <= p_tp
                    draws[l] += 1
                else:
                    positive = False
                a_uv = cr_end + d_cu
                row = SinkRow(
                    a_uv=a_uv, tick=k, grank=grank, slot=slot, lane=l, cam=c,
                    positive=positive, u=a_uv - now, q_bar=(0.0 + q_va) + q_cr,
                    va_fused=va_fused, va_end=va_end, cr_arr=cr_arr,
                    cr_fused=cr_fused, cr_end=cr_end,
                    mask=applied[:, c].copy(),
                    order=sink_order(va_line[l], cr_line[l], k, grank, slot,
                                     va_fused, cr_fused, cr_arr),
                )
                rows.append(row)
                pending.append(row)
        applied = new_req

    rows.sort(key=sink_sort_key)

    # Exec counts for the global batch-size books: a fused exec is counted
    # at its arrival (always before the horizon: sourcing stops at
    # duration); a queued exec is counted by the finish callback at its
    # end, which the scheduler only processes up to the horizon.
    va_execs = np.zeros(L, dtype=np.int64)
    cr_execs = np.zeros(L, dtype=np.int64)
    for r in rows:
        if r.va_fused or r.va_end <= horizon:
            va_execs[r.lane] += 1
        if r.cr_arr <= horizon and (r.cr_fused or r.cr_end <= horizon):
            cr_execs[r.lane] += 1

    return ChainOutput(
        rows=rows,
        source_events=g_source,
        positives_generated=g_pos,
        sourced=sourced,
        query_positives=query_pos,
        tl_counts=tl_counts,
        va_exec_counts=va_execs,
        cr_exec_counts=cr_execs,
        final_req=applied.copy(),
    )


def make_table_tl(plan) -> Callable[[int, List[SinkRow]], np.ndarray]:
    """Table-driven TL update for base/bfs/wbfs queries — the host mirror
    of the device scan's TL step.

    Plan attrs used: ``modes (N,) int8`` (0 base / 1 bfs / 2 wbfs),
    ``rgroup (N,) int``, ``r_tabs[g] (T, T) f64``, ``h_tabs[g] (T, T)
    int64``, ``cand_of_cam (C,) int``, ``dist_plane (n_cand, C) f64``,
    ``hop_plane (n_cand, C) int64``, ``seed_ls_cam (N,)``, ``num_cameras``.

    Radius/hop arithmetic lives entirely in the host-built tables
    (``R[i, j] = min_radius + speed * (f_j - f_i)``), so the per-tick update
    is pure comparisons and gathers — no float math to diverge on.
    """
    N = len(plan.modes)
    C = plan.num_cameras
    ls_cam = np.asarray(plan.seed_ls_cam, dtype=np.int64).copy()
    ls_tick = np.zeros(N, dtype=np.int64)
    modes = plan.modes
    rgroup = plan.rgroup
    cand_of_cam = plan.cand_of_cam
    dist_plane = plan.dist_plane
    hop_plane = plan.hop_plane
    r_tabs = plan.r_tabs
    h_tabs = plan.h_tabs

    def tl_step(k: int, dets: List[SinkRow]) -> np.ndarray:
        nonlocal ls_cam, ls_tick
        if dets:
            # Per query: the newest positive wins (max timestamp == max
            # source tick; python max keeps the first among equals, i.e.
            # the earliest in sink order).
            masks = np.stack([r.mask for r in dets])          # (M, N)
            pos = np.fromiter((r.positive for r in dets), dtype=bool, count=len(dets))
            ticks = np.fromiter((r.tick for r in dets), dtype=np.int64, count=len(dets))
            cand = masks & pos[:, None]                        # (M, N)
            any_pos = cand.any(axis=0)
            if any_pos.any():
                t_masked = np.where(cand, ticks[:, None], -1)
                best_tick = t_masked.max(axis=0)               # (N,)
                # First row in sink order among max-tick positives.
                hit = cand & (ticks[:, None] == best_tick[None, :])
                first = hit.argmax(axis=0)                     # (N,)
                cams = np.fromiter((r.cam for r in dets), dtype=np.int64, count=len(dets))
                ls_cam = np.where(any_pos, cams[first], ls_cam)
                ls_tick = np.where(any_pos, best_tick, ls_tick)
        else:
            any_pos = np.zeros(N, dtype=bool)

        req = np.zeros((N, C), dtype=bool)
        for q in range(N):
            mode = modes[q]
            if mode == 0:
                # TLBase: every camera stays active even on a positive (its
                # update only tracks last_seen, which nothing reads).
                req[q, :] = True
                continue
            if any_pos[q]:
                req[q, ls_cam[q]] = True
                continue
            g = rgroup[q]
            src = cand_of_cam[ls_cam[q]]
            if mode == 1:  # bfs hop ball
                hops = h_tabs[g][ls_tick[q], k]
                req[q] = hop_plane[src] <= hops
            else:          # wbfs weighted ball
                radius = r_tabs[g][ls_tick[q], k]
                req[q] = dist_plane[src] <= radius
        return req

    return tl_step
