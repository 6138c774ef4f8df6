"""Plain reference simulator of a deployment with drops on; imports nothing
of the platform.

It follows arXiv 1902.05577 sections 4.3-4.5 as the configuration states
them.  Cameras on the road graph source one frame per frame period while
some query's spotlight holds them.  Each frame goes FC -> VA -> CR -> sink,
every module instance one server with a FIFO run queue, and every hop costs
its network latency plus size over bandwidth.  Each instance keeps:

* a completion budget ``beta`` per downstream instance (section 4.3.4),
  unassigned (no drops) until the first signal sets it;
* three drop points: before queuing (dp1, ``u + xi(1) > beta``), before
  execution over the formed batch (dp2, ``u + q + xi(b) > beta``) and
  before transmit toward the chosen downstream (dp3, ``u + pi > beta``);
  frames flagged ``avoid_drop`` and probes pass all three;
* the dynamic batcher of section 4.4 (at VA and CR): the head event joins
  the open batch iff ``t + xi(m + 1) <= min(Delta_p, delta_x)``, else the
  batch is submitted; a batch auto-submits at ``Delta_p - xi(m)``;
* the reject and accept updates of section 4.5: a drop sends ``epsilon``
  and the upstream queuing ``q_bar`` to every instance the frame passed,
  which lowers its budget to ``min(d - lam, beta)`` with ``lam =
  min(epsilon * q / q_bar, xi(m) - xi(1))``; the sink sends an accept for
  the slowest frame of each CR batch that arrives more than
  ``epsilon_max`` inside gamma, which raises it to ``max(d + lam, beta)``
  with ``lam = min(epsilon * xi(m) / xi_bar, (m_max - m) * q / m +
  xi(m_max) - xi(m))``; where ``epsilon`` is 0 the share is 0.

Where the platform's rule differs from the paper, this simulator follows
the platform:

* bootstrap: while an instance's budget is unassigned, every arrival is a
  batch of one (an open batch is submitted first), so batches grow only
  once a signal has set a budget;
* dp2 keeps ``xi(b)`` of the full batch while it filters the batch, and
  the batch then runs at ``xi`` of what is left;
* every 16th drop of an instance re-enters that instance as a probe that
  no drop point drops and no module logic sees; a probe that reaches the
  sink within gamma sends an accept along its path;
* one budget per downstream instance, and the drop points before the
  destination is known (dp1, dp2) use the least of them;
* the FC instance of each camera is a static batcher of one whose budget
  toward its VA is kept like the others; the frames that hold the entity
  are flagged ``avoid_drop`` when FC runs them, a VA frame when its re-ID
  matches a live query, and a CR frame when its verdict is positive
  (with ``avoid_drop_positives``);
* records of ``<d, q, m>`` are kept for the newest 4096 events of an
  instance, and the downstream each event went to for the newest 8192;
* events due at the same instant run in the order they were scheduled.

VA runs re-ID of each batch's frames (those that are not probes) against
the live queries' embeddings, one dispatch per batch, in float64; CR gives
a positive verdict on an entity frame with probability
``p_true_positive``; each frame period the tracking logic contracts a
query's spotlight to the camera of its newest positive, or grows it to the
cameras within ``ceil(speed * (now - last seen) / 84.5)`` road hops
(TL-BFS, a fixed edge length), and the camera changes land one MAN latency
later.  The world is ``refsim``'s: the deployment's data, made from its
seed by the generators the deployment documents.

Time is float64; ``time32=True`` keeps every event time in float32, each
rounded up (the precision control of the time guarantee).  As a reference module
(``bench/run.py::reference_books``) it offers ``refuses(config, plans)``
and ``books(config, plans, cuts, *, time32=False)``.
"""

from __future__ import annotations

import heapq
import math
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .refsim import Query, World

#: Assumed road length per hop of TL-BFS, metres (the paper's mean edge).
BFS_EDGE_M = 84.5
PROBE_EVERY = 16
RECORDS = 4096
DOWNSTREAMS = 8192
DUE_EPS = 1e-6  # an auto-submit up to 1 us early counts as due


def up_to_float32(t: float) -> float:
    """The least float32 at or after ``t``: an event never runs before the
    time it was due, so an auto-submit timer finds its batch due."""
    t32 = np.float32(t)
    return float(t32) if float(t32) >= t else float(np.nextafter(t32, np.float32(np.inf)))


def linear_xi(cost):
    c0, c1 = float(cost[0]), float(cost[1])
    return lambda b: c0 + c1 * max(int(b), 0)


class Frame:
    """One frame in flight, with what its header carries."""

    __slots__ = ("eid", "src", "xi_bar", "q_bar", "avoid", "probe", "path", "cam", "has",
                 "emb", "mask", "slowest", "positive")

    def __init__(self, eid: int, src: float, cam: int, has: bool, emb, mask: int) -> None:
        self.eid, self.src, self.cam, self.has, self.emb, self.mask = eid, src, cam, has, emb, mask
        self.xi_bar = self.q_bar = 0.0
        self.avoid = self.probe = self.slowest = self.positive = False
        self.path: tuple = ()

    def as_probe(self) -> "Frame":
        p = Frame(self.eid, self.src, self.cam, self.has, self.emb, 0)
        p.xi_bar, p.q_bar, p.path, p.probe = self.xi_bar, self.q_bar, self.path, True
        return p


class Budget:
    """Section 4.5: the budgets of one instance, one per downstream, and the
    records ``<d, q, m, xi(m)>`` of the events it ran."""

    def __init__(self, xi, m_max: int) -> None:
        self.xi, self.m_max = xi, m_max
        self.records: "OrderedDict[int, tuple]" = OrderedDict()
        self.beta: Dict[str, Optional[float]] = {}  # None: unassigned

    def record(self, eid: int, d: float, q: float, m: int, xi_m: float) -> None:
        if eid in self.records:
            self.records.move_to_end(eid)
        self.records[eid] = (d, q, m, xi_m)
        if len(self.records) > RECORDS:
            self.records.popitem(last=False)

    def toward(self, dst: str) -> float:
        v = self.beta.setdefault(dst, None)
        return math.inf if v is None else v

    def least(self) -> float:
        return min((math.inf if v is None else v for v in self.beta.values()), default=math.inf)

    def reject(self, eid: int, eps: float, q_bar: float, dst: str) -> None:
        rec = self.records.get(eid)
        if rec is None:
            return
        d, q, m, _ = rec
        lam = 0.0
        if q_bar > 0.0 and eps:
            lam = min(eps * (q / q_bar), max(self.xi(m) - self.xi(1), 0.0))
        old = self.beta.get(dst)
        self.beta[dst] = d - lam if old is None else min(d - lam, old)

    def accept(self, eid: int, eps: float, xi_bar: float, dst: str) -> None:
        rec = self.records.get(eid)
        if rec is None:
            return
        d, q, m, xi_m = rec
        share = eps * (xi_m / xi_bar) if xi_bar > 0.0 and eps else 0.0
        m = max(m, 1)
        headroom = (self.m_max - m) * (q / m) + self.xi(self.m_max) - self.xi(m)
        lam = min(share, max(headroom, 0.0))
        old = self.beta.get(dst)
        self.beta[dst] = d + lam if old is None else max(d + lam, old)


class Instance:
    """One module instance: drop points, batcher, one server, budgets."""

    def __init__(self, sim: "Reference", name: str, module: str, node: str, xi, m_max: int,
                 out_bytes: float, cam: int = -1) -> None:
        self.sim, self.name, self.module, self.node, self.cam = sim, name, module, node, cam
        self.xi, self.m_max, self.out_bytes = xi, m_max, out_bytes
        self.dynamic = module != "FC"  # FC: a static batch of one
        self.budget = Budget(xi, m_max)
        self.went: "OrderedDict[int, str]" = OrderedDict()  # event -> downstream
        self.open: List[tuple] = []  # the open batch: (frame, arrival, deadline)
        self.delta = math.inf  # the open batch's deadline Delta_p
        self.timer = False
        self.busy = False
        self.runq: deque = deque()
        self.drops = 0
        self.n = dict(dp1=0, dp2=0, dp3=0, arrived=0, executed=0, batches=0)

    # ---- arrival, drop point 1, the batcher ------------------------------- #
    def arrive(self, f: Frame) -> None:
        now = self.sim.now
        self.n["arrived"] += 1
        beta = self.budget.least()
        if not (f.avoid or f.probe) and (now - f.src) + self.xi(1) > beta:
            self.n["dp1"] += 1
            self.drop(f, (now - f.src) + self.xi(1) - beta, 1)
            return
        pe = (f, now, f.src + beta)
        if not self.dynamic:
            self.open.append(pe)
            batch, self.open = self.open, []
            self.enqueue(batch)
            return
        if beta == math.inf:  # bootstrap: a batch of one
            if self.open:
                self.enqueue(self.take())
            self.enqueue([pe])
            return
        m = len(self.open)
        fits = now + self.xi(m + 1) <= min(self.delta, pe[2])
        submit = self.take() if m > 0 and not fits else None
        self.open.append(pe)
        self.delta = min(self.delta, pe[2])
        if len(self.open) >= self.m_max:
            full = self.take()
            submit = full if submit is None else submit + full
        if submit:
            self.enqueue(submit)
        self.arm()

    def take(self) -> List[tuple]:
        batch, self.open, self.delta = self.open, [], math.inf
        return batch

    def due(self) -> float:
        return self.delta - self.xi(len(self.open)) if self.open else math.inf

    def arm(self) -> None:
        if self.timer or math.isinf(self.due()):
            return
        self.timer = True
        self.sim.after(max(self.due() - self.sim.now, 0.0), self.fire)

    def fire(self) -> None:
        self.timer = False
        if self.open and self.sim.now >= self.due() - DUE_EPS:
            self.enqueue(self.take())
        self.arm()

    # ---- drop point 2 and the server --------------------------------------- #
    def enqueue(self, batch: List[tuple]) -> None:
        self.runq.append(batch)
        self.run()

    def run(self) -> None:
        if self.busy:
            return
        while self.runq:
            batch = self.runq.popleft()
            now = self.sim.now
            xi_b, beta = self.xi(len(batch)), self.budget.least()
            keep = [f.avoid or f.probe or (a - f.src) + (now - a) + xi_b <= beta
                    for f, a, _ in batch]
            if not all(keep):
                for (f, a, _), k in zip(batch, keep):
                    if not k:
                        self.n["dp2"] += 1
                        self.drop(f, (a - f.src) + (now - a) + xi_b - beta, 2)
                batch = [pe for pe, k in zip(batch, keep) if k]
                if not batch:
                    continue
            dur = self.xi(len(batch))
            self.busy = True
            self.sim.after(dur, self.finish, batch, now, dur)
            return

    def finish(self, batch: List[tuple], start: float, dur: float) -> None:
        self.n["batches"] += 1
        m = len(batch)
        work = [pe[0] for pe in batch if not pe[0].probe]
        out = set(map(id, self.sim.logic(self, work)))
        out |= {id(pe[0]) for pe in batch if pe[0].probe}
        slowest, worst = None, -math.inf
        if m > 1 or batch[0][0].probe:
            for f, a, _ in batch:
                if (a - f.src) + ((start - a) + dur) > worst:
                    worst, slowest = (a - f.src) + ((start - a) + dur), f.eid
        for f, a, _ in batch:
            u, q = a - f.src, start - a
            pi = q + dur
            self.n["executed"] += 1
            self.budget.record(f.eid, u + pi, q, m, dur)
            if id(f) not in out:
                continue
            f.xi_bar += dur
            f.q_bar += q
            f.path = f.path + (self.name,)
            if m == 1 and not f.probe or f.eid == slowest:
                f.slowest = True
            self.route(f, u, pi)
        self.busy = False
        self.run()

    # ---- drop point 3 and the hop ------------------------------------------ #
    def route(self, f: Frame, u: float, pi: float) -> None:
        sim = self.sim
        dst = sim.downstream(self, f)
        self.went[f.eid] = dst.name
        if len(self.went) > DOWNSTREAMS:
            self.went.popitem(last=False)
        beta = self.budget.toward(dst.name)
        if not (f.avoid or f.probe) and u + pi > beta:
            self.n["dp3"] += 1
            self.drop(f, u + pi - beta, 3)
            return
        sim.after(sim.hop(self.node, dst.node, self.out_bytes), dst.arrive, f)

    # ---- signals ------------------------------------------------------------ #
    def drop(self, f: Frame, eps: float, point: int) -> None:
        sim = self.sim
        self.drops += 1
        sim.charge_drop(f, point)
        # Only a frame FC has not yet run has an empty path, and FC has no
        # upstream: a reject goes to the instances the frame passed.
        for name in f.path:
            if name != self.name:
                sim.tasks[name].on_reject(f.eid, max(eps, 0.0), f.q_bar)
        if self.drops % PROBE_EVERY == 0:
            sim.after(0.0, self.arrive, f.as_probe())

    def on_reject(self, eid: int, eps: float, q_bar: float) -> None:
        self.budget.reject(eid, eps, q_bar, self.went.get(eid, ""))

    def on_accept(self, eid: int, eps: float, xi_bar: float) -> None:
        self.budget.accept(eid, eps, xi_bar, self.went.get(eid, ""))


class Sink:
    name, node = "UV", "head"

    def __init__(self, sim: "Reference") -> None:
        self.sim = sim

    def arrive(self, f: Frame) -> None:
        self.sim.sink(f)


class Reference:
    """One replay of the deployment ``config`` under the query plans
    ``plans`` (all submitted at t = 0 and kept to the end)."""

    def __init__(self, config: Dict[str, Any], world: World, plans: Sequence[Dict],
                 *, time32: bool = False) -> None:
        scn, net = config["scenario"], config["network"]
        self.w = world
        self.rnd = up_to_float32 if time32 else (lambda x: x)
        self.duration = float(scn["duration_s"])
        self.period = 1.0 / float(scn["fps"])
        self.tl_period = float(scn["tl_update_period"])
        self.gamma = float(scn["gamma"])
        self.eps_max = float(scn["epsilon_max"])
        self.horizon = self.duration + 3.0 * self.gamma
        self.min_radius = float(scn["tl_min_radius_m"])
        self.p_tp = float(scn["p_true_positive"])
        self.thr = float(scn["reid_threshold"])
        self.dim = int(scn.get("embed_dim", 0))
        self.avoid_positives = bool(scn.get("avoid_drop_positives"))
        self.lat = float(net["man_latency_s"])
        self.net = net
        m_max, nodes = int(scn["m_max"]), int(scn["num_nodes"])
        self.fc_xi = linear_xi(scn["fc_cost"])
        va_xi, cr_xi = linear_xi(scn["va_cost"]), linear_xi(scn["cr_cost"])
        self.va = [Instance(self, f"VA-{i}", "VA", f"node{i % nodes}", va_xi, m_max,
                            float(net["frame_bytes"])) for i in range(int(scn["num_va"]))]
        self.cr = [Instance(self, f"CR-{i}", "CR", f"node{i % nodes}", cr_xi, m_max,
                            float(net["detection_bytes"])) for i in range(int(scn["num_cr"]))]
        self.fc: Dict[int, Instance] = {}
        self.tasks: Dict[str, Instance] = {t.name: t for t in self.va + self.cr}
        self.uv = Sink(self)
        self.cr_rng = {t.name: np.random.default_rng(int(scn["seed"]) + 101) for t in self.cr}
        self.cam_rng = np.random.default_rng(int(scn["seed"]) + 13)
        self.entity_emb = (self.cam_rng.normal(size=(self.dim,)).astype(np.float32)
                           if self.dim else None)
        self._hops: Dict[int, np.ndarray] = {}

        self.now = 0.0
        self._heap: List[tuple] = []
        self._seq = 0
        self._eid = 0
        self.queries = [Query(i, p) for i, p in enumerate(plans)]
        for q in self.queries:
            q.x = dict(dropped=0, orphan_dropped=0, dp={"dp1": 0, "dp2": 0, "dp3": 0})
        self.mask_of: Dict[int, int] = {}
        self.lit: set = set()
        self.target: set = set()
        self.pending: List[tuple] = []
        self.g = dict(source_events=0, positives_generated=0, positives_completed=0,
                      detections_on_time=0, on_time=0, delayed=0, reid_matched=0,
                      reid_dispatches=0)
        self.g_latencies: List[tuple] = []
        self.g_timeline: List[tuple] = []
        self.gallery: List[bytes] = []
        for q in self.queries:
            self.activate(q)
        self.after(0.0, self.frame_tick)
        self.after(self.tl_period, self.tl_tick)

    # ---- the event loop ------------------------------------------------ #
    def after(self, delay: float, fn, *args) -> None:
        t = self.now + delay if delay > 0.0 else self.now
        self._seq += 1
        heapq.heappush(self._heap, (self.rnd(t), self._seq, fn, args))

    def run_until(self, t: float) -> "Reference":
        t = min(t, self.horizon)
        while self._heap and self._heap[0][0] <= t:
            self.now, _, fn, args = heapq.heappop(self._heap)
            fn(*args)
        return self

    def hop(self, src: str, dst: str, size: float) -> float:
        net = self.net
        if src == dst:
            return float(net["ipc_latency_s"])
        man = src.startswith("edge") or dst.startswith("edge")
        return (float(net["man_latency_s" if man else "lan_latency_s"])
                + size * 8.0 / float(net["lan_bandwidth_bps"]))

    def downstream(self, task: Instance, f: Frame):
        if task.module == "FC":
            return self.va[f.cam % len(self.va)]
        if task.module == "VA":
            return self.cr[f.cam % len(self.cr)]
        return self.uv

    # ---- queries and the tracking logic ---------------------------------- #
    def activate(self, q: Query) -> None:
        q.last_cam, q.last_t = self.w.nearest_camera(self.now), self.now
        q.requested = self.spotlight(q)
        if self.dim:
            q.emb = (self.entity_emb if q.embedding_seed is None else
                     np.random.default_rng(q.embedding_seed).normal(size=(self.dim,))
                     .astype(np.float32))
        q.state = "scoped"
        for cam in q.requested:
            self.apply(q, cam, True)
            self.lit.add(cam)
        self.target |= q.requested

    def apply(self, q: Query, cam: int, on: bool) -> None:
        if on:
            q.applied.add(cam)
            self.mask_of[cam] = self.mask_of.get(cam, 0) | q.bit
        else:
            q.applied.discard(cam)
            self.mask_of[cam] = self.mask_of.get(cam, 0) & ~q.bit

    def switch(self, cam: int, on: bool) -> None:
        if on:
            self.lit.add(cam)
            self.fc_task(cam)
        else:
            self.lit.discard(cam)

    def hops_from(self, cam: int) -> np.ndarray:
        """Road hops from ``cam``'s vertex to every camera's (BFS)."""
        src = int(self.w.cam_vertex[cam])
        if src not in self._hops:
            adj = self.w.adjacency
            dist = np.full(len(adj), np.iinfo(np.int64).max)
            dist[src] = 0
            frontier = [src]
            while frontier:
                nxt = []
                for u in frontier:
                    for v, _ in adj[u]:
                        if dist[v] > dist[u] + 1:
                            dist[v] = dist[u] + 1
                            nxt.append(v)
                frontier = nxt
            self._hops[src] = dist[self.w.cam_vertex]
        return self._hops[src]

    def spotlight(self, q: Query) -> set:
        radius = self.min_radius + q.speed * max(self.now - q.last_t, 0.0)
        inside = self.hops_from(q.last_cam) <= int(math.ceil(radius / BFS_EDGE_M))
        return {int(c) for c in np.nonzero(inside)[0]}

    def tl_tick(self) -> None:
        dets, self.pending = self.pending, []
        union = set()
        for q in [q for q in self.queries if q.live]:
            seen = [d for d in dets if d[3] & q.bit and d[1]]
            if seen:
                newest = max(seen, key=lambda d: d[2])
                q.last_cam, q.last_t = newest[0], newest[2]
                new = {newest[0]}
            else:
                new = self.spotlight(q)
            q.timeline.append((self.now, len(new)))
            for cam in sorted(new - q.requested):
                self.after(self.lat, self.apply, q, cam, True)
            for cam in sorted(q.requested - new):
                self.after(self.lat, self.apply, q, cam, False)
            q.requested = new
            union |= new
        self.g_timeline.append((self.now, len(union)))
        for cam in sorted(union - self.target):
            self.after(self.lat, self.switch, cam, True)
        for cam in sorted(self.target - union):
            self.after(self.lat, self.switch, cam, False)
        self.target = union
        if self.now + self.tl_period <= self.duration:
            self.after(self.tl_period, self.tl_tick)

    # ---- frames and the module logic --------------------------------------- #
    def fc_task(self, cam: int) -> Instance:
        t = self.fc.get(cam)
        if t is None:
            t = self.fc[cam] = Instance(self, f"FC-{cam}", "FC", f"edge{cam}", self.fc_xi, 1,
                                        float(self.net["frame_bytes"]), cam)
            self.tasks[t.name] = t
        return t

    def frame_tick(self) -> None:
        t = self.now
        if self.lit:
            pos = self.w.walk.position(t)
            frames = []
            for cam in sorted(self.lit):
                has = self.w.visible(cam, t, pos)
                emb = None
                if self.dim:
                    emb = ((self.entity_emb + self.cam_rng.normal(scale=0.1, size=(self.dim,)))
                           if has else self.cam_rng.normal(size=(self.dim,))).astype(np.float32)
                mask = self.mask_of.get(cam, 0)
                if mask:
                    self._eid += 1
                    frames.append(Frame(self._eid, t, cam, has, emb, mask))
            for f in frames:
                self.g["source_events"] += 1
                self.g["positives_generated"] += f.has
                for q in self.queries:
                    if f.mask & q.bit:
                        q.n["sourced"] += 1
                        q.n["positives_generated"] += f.has
                self.fc_task(f.cam).arrive(f)
        if t + self.period <= self.duration:
            self.after(self.period, self.frame_tick)

    def logic(self, task: Instance, work: List[Frame]) -> List[Frame]:
        """The module's logic over a batch's frames (probes excluded); the
        frames it passes on."""
        if task.module == "FC":
            if task.cam not in self.lit:  # FC forwards while its camera is on
                return []
        elif task.module == "VA":
            self.reid(work)
        else:
            rng = self.cr_rng[task.name]
            for f in work:
                f.positive = bool(f.has) and float(rng.uniform()) <= self.p_tp
                f.slowest = False
                f.avoid = f.avoid or (self.avoid_positives and f.positive)
            return work
        if self.avoid_positives:
            for f in work:
                f.avoid = f.avoid or bool(f.has)
        return work

    def reid(self, frames: List[Frame]) -> None:
        block = [q for q in self.queries if q.live and q.emb is not None]
        rows = [f for f in frames if f.emb is not None]
        if not block or not rows:
            return
        self.g["reid_dispatches"] += 1
        for f in rows:
            self.gallery.append(f.emb.tobytes())
            g = f.emb.astype(np.float64)
            g = g / max(np.linalg.norm(g), 1e-6)
            hit = False
            for q in block:
                if f.mask & q.bit:
                    e = q.emb.astype(np.float64)
                    if float(g @ (e / max(np.linalg.norm(e), 1e-6))) >= self.thr:
                        q.n["reid_matched"] += 1
                        hit = True
            if hit:
                self.g["reid_matched"] += 1
                f.avoid = f.avoid or self.avoid_positives

    # ---- drops, the sink, accepts -------------------------------------------- #
    def charge_drop(self, f: Frame, point: int) -> None:
        for q in self.queries:
            if f.mask & q.bit:
                if q.live:
                    q.x["dropped"] += 1
                    q.x["dp"][f"dp{point}"] += 1
                else:
                    q.x["orphan_dropped"] += 1

    def accept(self, f: Frame, eps: float) -> None:
        for name in f.path:
            self.tasks[name].on_accept(f.eid, eps, f.xi_bar)

    def sink(self, f: Frame) -> None:
        u = self.now - f.src
        if f.probe:
            if u <= self.gamma:
                self.accept(f, self.gamma - u)
            return
        ok = u <= self.gamma
        self.g_latencies.append((self.now, u))
        self.g["on_time" if ok else "delayed"] += 1
        if f.slowest and self.gamma - u > self.eps_max:
            self.accept(f, self.gamma - u)
        if f.positive:
            self.g["positives_completed"] += 1
            self.g["detections_on_time"] += ok
        self.pending.append((f.cam, f.positive, f.src, f.mask))
        for q in self.queries:
            if not f.mask & q.bit:
                continue
            if not q.live:
                q.n["orphan_completed"] += 1
                continue
            q.n["completed"] += 1
            q.latencies.append((self.now, u))
            q.n["on_time" if ok else "delayed"] += 1
            if f.positive:
                q.n["positives_completed"] += 1
                q.n["detections_on_time"] += ok
                if q.state == "scoped":
                    q.state, q.found_at = "found", self.now

    # ---- the books ------------------------------------------------------- #
    def observe(self) -> Dict[str, Any]:
        """Books as of the simulated time reached, in ``check``'s shape."""
        def total(tasks):
            keys = ("dp1", "dp2", "dp3", "arrived", "executed", "batches")
            return {k: sum(t.n[k] for t in tasks) for k in keys}

        modules = {"VA": total(self.va), "CR": total(self.cr)}
        if self.fc:
            modules = dict(FC=total(self.fc.values()), **modules)
        exact = {"modules": modules, "per": {}, "global": dict(self.g),
                 "gallery": sorted(self.gallery), "timeline": list(self.g_timeline)}
        timed = {"global": sorted(self.g_latencies), "per": {}}
        for q in self.queries:
            exact["per"][q.qid] = dict(q.n, dropped=q.x["dropped"],
                                       orphan_dropped=q.x["orphan_dropped"],
                                       dp=dict(q.x["dp"]), state=q.state, ended_at=q.ended_at,
                                       found=q.found_at is not None,
                                       timeline=list(q.timeline),
                                       requested=sorted(q.requested),
                                       applied=sorted(q.applied))
            timed["per"][q.qid] = sorted(q.latencies) + (
                [(q.found_at, 0.0)] if q.found_at is not None else [])
        return {"exact": exact, "timed": timed}


# --------------------------------------------------------------------- #
# The reference contract                                                  #
# --------------------------------------------------------------------- #
#: Scenario keys the simulator reads, or knows to change nothing in what it
#: simulates (``static_batch`` with dynamic batching; ``tl``, which the
#: query plans override).
KNOWN = {"num_cameras", "duration_s", "fps", "entity_speed_mps", "fov_radius_m", "seed",
         "road_vertices", "gamma", "epsilon_max", "tl", "tl_update_period",
         "tl_min_radius_m", "batching", "static_batch", "m_max", "drops_enabled",
         "avoid_drop_positives", "num_va", "num_cr", "num_nodes", "fc_cost", "va_cost",
         "cr_cost", "p_true_positive", "embed_dim", "reid_threshold"}


def refuses(config: Dict[str, Any], plans: Sequence[Dict]) -> Optional[str]:
    """Why this simulator cannot judge ``config`` under ``plans``, or None."""
    scn = config["scenario"]
    why = []
    tls = sorted({p["tl"] for p in plans} - {"bfs"})
    if tls:
        why.append(f"queries with TL {tls} (it simulates TL-BFS only)")
    if any(p["submit_at"] > 0.0 or p["ttl_s"] is not None for p in plans):
        why.append("queries submitted after t = 0 or with a ttl (it simulates queries "
                   "opened at t = 0 and kept to the end)")
    if not scn.get("drops_enabled"):
        why.append("drops_enabled: false (it simulates drops on; refsim judges drops off)")
    if scn.get("batching", "dynamic") != "dynamic":
        why.append(f"batching {scn['batching']!r} (it simulates dynamic batching)")
    unknown = sorted(set(scn) - KNOWN)
    if unknown:
        why.append(f"scenario keys it does not simulate: {unknown}")
    return "dropsim refuses " + "; ".join(why) if why else None


def books(config: Dict[str, Any], plans: Sequence[Dict], cuts: Sequence[float], *,
          time32: bool = False) -> Dict[float, Dict[str, Any]]:
    """The books of one replay at each simulated time of ``cuts`` (the
    horizon for a finished replay)."""
    reason = refuses(config, plans)
    if reason is not None:
        raise ValueError(reason)
    sim = Reference(config, World(config["scenario"]), plans, time32=time32)
    return {t: sim.run_until(t).observe() for t in sorted(set(cuts))}
