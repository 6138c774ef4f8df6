"""Plain reference simulator of a cell's deployment; imports nothing of the
platform.

It follows the pipeline of arXiv 1902.05577 as the configuration states
it: cameras on the road graph source one frame per frame period while some
query's spotlight holds them; each frame goes FC -> VA -> CR -> sink, every
module instance a FIFO server with the affine cost ``c0 + c1`` of a batch of
one (drops off, so dynamic batching never grows a batch), FC folded into the
hop to VA, every hop its network latency plus size over bandwidth;
VA runs re-ID of the frame's embedding against each live query's; CR gives
a positive verdict on an entity frame with probability ``p_true_positive``;
each frame period the tracking logic (TL-WBFS) contracts a query's
spotlight to the camera of its newest positive, or grows it to every camera
within ``speed * (now - last seen)`` metres of road, and the camera changes
land one MAN latency later.  Queries arrive, are found and expire on their
own schedule.

The world (road graph, the entity's walk, the camera placement and the
embedding draws) is the deployment's data, made from its seed by the same
generator the deployment documents.  Time is float64; ``time32=True`` keeps
every event time in float32 instead (the precision control of the time
guarantee).  ``observe()`` gives the books as of the simulated time reached.

As a reference module (``bench/run.py::reference_books``) it offers
``refuses(config, plans)``, which names what the configuration asks for and
this simulator does not model, and ``books(config, plans, cuts)``.
"""

from __future__ import annotations

import heapq
import math
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


# --------------------------------------------------------------------- #
# The world: road graph, the entity's walk, the cameras                  #
# --------------------------------------------------------------------- #
def road_network(num_vertices: int, target_edges: int, mean_length_m: float, seed: int):
    """A random geometric road graph of the paper's statistics: vertices in
    a disc of 7 km^2, each joined to its nearest neighbours until the edge
    budget is met, components joined by their closest pair, lengths scaled
    to the mean.  Returns ``(positions, adjacency)``."""
    rng = np.random.default_rng(seed)
    radius = math.sqrt(7.0e6 / math.pi)
    r = radius * np.sqrt(rng.uniform(0.0, 1.0, size=num_vertices))
    theta = rng.uniform(0.0, 2.0 * math.pi, size=num_vertices)
    pos = np.stack([r * np.cos(theta), r * np.sin(theta)], axis=1)

    def d2(us, vs):
        return np.sum((pos[us][:, None, :] - pos[vs][None, :, :]) ** 2, axis=-1)

    k = max(2, int(math.ceil(2.0 * target_edges / num_vertices)) + 1)
    knn = np.empty((num_vertices, k), dtype=np.int64)
    rows = max(1, int(2**22 // num_vertices))
    every = np.arange(num_vertices)
    for s in range(0, num_vertices, rows):
        e = min(s + rows, num_vertices)
        block = d2(every[s:e], every)
        block[np.arange(e - s), np.arange(s, e)] = np.inf
        part = np.argpartition(block, k - 1, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(block, part, axis=1), axis=1, kind="stable")
        knn[s:e] = np.take_along_axis(part, order, axis=1)

    edges = set()
    for u in range(num_vertices):
        v = int(knn[u, 0])
        edges.add((min(u, v), max(u, v)))
    for rank in range(1, k):
        if len(edges) >= target_edges:
            break
        for u in range(num_vertices):
            if len(edges) >= target_edges:
                break
            v = int(knn[u, rank])
            edges.add((min(u, v), max(u, v)))

    parent = list(range(num_vertices))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in edges:
        parent[find(u)] = find(v)
    while len({find(u) for u in range(num_vertices)}) > 1:
        comp: Dict[int, List[int]] = {}
        for u in range(num_vertices):
            comp.setdefault(find(u), []).append(u)
        comps = list(comp.values())
        base = np.asarray(comps[0])
        best = (math.inf, -1, -1)
        for other in comps[1:]:
            other = np.asarray(other)
            block = d2(base, other)
            bi, oi = divmod(int(np.argmin(block)), len(other))
            if float(block[bi, oi]) < best[0]:
                best = (float(block[bi, oi]), int(base[bi]), int(other[oi]))
        _, u, v = best
        edges.add((min(u, v), max(u, v)))
        parent[find(u)] = find(v)

    def length(u, v):
        a, b = pos[u, 0] - pos[v, 0], pos[u, 1] - pos[v, 1]
        return math.sqrt(a * a + b * b)

    scale = mean_length_m / (sum(length(u, v) for u, v in edges) / len(edges))
    adjacency: List[List[tuple]] = [[] for _ in range(num_vertices)]
    for u, v in sorted(edges):
        w = length(u, v) * scale
        adjacency[u].append((v, w))
        adjacency[v].append((u, w))
    return pos * scale, adjacency


class Walk:
    """The entity's random walk along the roads at a fixed speed from
    vertex 0, never turning straight back where it has a choice."""

    def __init__(self, positions, adjacency, speed: float, duration_s: float, seed: int):
        rng = np.random.default_rng(seed)
        times, verts = [0.0], [0]
        t, u, prev = 0.0, 0, -1
        while t < duration_s:
            choices = [(v, w) for v, w in adjacency[u] if v != prev] or list(adjacency[u])
            v, w = choices[int(rng.integers(len(choices)))]
            t += w / speed
            times.append(t)
            verts.append(v)
            prev, u = u, v
        self.times = np.asarray(times)
        self.start = positions[verts[0]]
        self.p0 = positions[np.asarray(verts[:-1])]
        self.p1 = positions[np.asarray(verts[1:])]

    def position(self, t: float) -> np.ndarray:
        i = int(np.searchsorted(self.times, t, side="right")) - 1
        i = max(0, min(i, len(self.times) - 2))
        t0, t1 = float(self.times[i]), float(self.times[i + 1])
        a = 0.0 if t1 <= t0 else min(max((t - t0) / (t1 - t0), 0.0), 1.0)
        return self.p0[i] * (1 - a) + self.p1[i] * a


class World:
    """Road graph, walk and cameras of one deployment (``scenario`` group
    of its configuration file)."""

    def __init__(self, scn: Dict[str, Any]) -> None:
        cams = int(scn["num_cameras"])
        vertices = scn.get("road_vertices") or max(1000, cams)
        edges = 2817 if vertices == 1000 else int(round(vertices * 2.817))
        seed = int(scn["seed"])
        self.positions, self.adjacency = road_network(vertices, edges, 84.5, seed)
        self.walk = Walk(self.positions, self.adjacency, float(scn["entity_speed_mps"]),
                         float(scn["duration_s"]) + 60.0, seed + 7)
        near = np.argsort(np.sum((self.positions - self.walk.start) ** 2, axis=1))
        self.cam_vertex = near[:min(cams, vertices)].astype(np.int64)
        self.cam_pos = self.positions[self.cam_vertex]
        self.fov = float(scn["fov_radius_m"])
        self._dist: Dict[int, np.ndarray] = {}

    def visible(self, cam: int, t: float, pos: np.ndarray) -> bool:
        return float(np.linalg.norm(pos - self.cam_pos[cam])) <= self.fov

    def nearest_camera(self, t: float) -> int:
        pos = self.walk.start if t <= 0.0 else self.walk.position(t)
        return int(np.argmin(np.linalg.norm(self.cam_pos - pos, axis=1)))

    def road_distance(self, cam: int) -> np.ndarray:
        """Road distance from ``cam``'s vertex to every camera's (Dijkstra)."""
        src = int(self.cam_vertex[cam])
        if src not in self._dist:
            dist = np.full(len(self.adjacency), np.inf)
            dist[src] = 0.0
            heap = [(0.0, src)]
            while heap:
                d, u = heapq.heappop(heap)
                if d > dist[u]:
                    continue
                for v, w in self.adjacency[u]:
                    if d + w < dist[v]:
                        dist[v] = d + w
                        heapq.heappush(heap, (d + w, v))
            self._dist[src] = dist[self.cam_vertex]
        return self._dist[src]


# --------------------------------------------------------------------- #
# The pipeline                                                           #
# --------------------------------------------------------------------- #
class Query:
    def __init__(self, qid: int, plan: Dict[str, Any]) -> None:
        self.qid, self.bit = qid, 1 << qid
        self.speed = float(plan["tl_peak_speed"])
        self.submit_at = float(plan["submit_at"])
        self.ttl_s = plan["ttl_s"]
        self.embedding_seed = plan["embedding_seed"]
        self.state = "submitted"
        self.found_at: Optional[float] = None
        self.ended_at: Optional[float] = None
        self.emb: Optional[np.ndarray] = None
        self.last_cam = -1
        self.last_t = 0.0
        self.requested: set = set()
        self.applied: set = set()
        self.latencies: List[tuple] = []
        self.timeline: List[tuple] = []
        self.n = dict(sourced=0, positives_generated=0, completed=0, on_time=0,
                      delayed=0, positives_completed=0, detections_on_time=0,
                      orphan_completed=0, reid_matched=0)

    @property
    def live(self) -> bool:
        return self.state in ("scoped", "found")


NO_DROPS = {"dp1": 0, "dp2": 0, "dp3": 0}


class Server:
    """One module instance: a FIFO server taking one frame at a time.  Its
    books count a frame served on arrival as executed when its service
    starts, and a frame that queued when its service ends, as the platform
    counts them."""

    def __init__(self, xi: float, node: str) -> None:
        self.xi, self.node = xi, node
        self.free_at = -math.inf
        self.queue: deque = deque()
        self.waking = False
        self.arrived = 0
        self.executed = 0


class Reference:
    """One replay of the deployment ``config`` (its ``scenario`` and
    ``network`` groups) under the query plans ``plans``."""

    def __init__(self, config: Dict[str, Any], world: World, plans: Sequence[Dict],
                 *, time32: bool = False) -> None:
        scn, net = config["scenario"], config["network"]
        self.w = world
        self.rnd = (lambda x: float(np.float32(x))) if time32 else (lambda x: x)
        self.duration = float(scn["duration_s"])
        self.period = 1.0 / float(scn["fps"])
        self.tl_period = float(scn["tl_update_period"])
        self.gamma = float(scn["gamma"])
        self.horizon = self.duration + 3.0 * self.gamma
        self.min_radius = float(scn["tl_min_radius_m"])
        self.p_tp = float(scn["p_true_positive"])
        self.thr = float(scn["reid_threshold"])
        self.dim = int(scn["embed_dim"])
        self.lat = float(net["man_latency_s"])
        bw = float(net["lan_bandwidth_bps"])

        def hop(src: str, dst: str, size: float) -> float:
            if src == dst:
                return float(net["ipc_latency_s"])
            man = src.startswith("edge") or dst.startswith("edge")
            return float(net["man_latency_s" if man else "lan_latency_s"]) + size * 8.0 / bw

        def xi(cost) -> float:
            return cost[0] + cost[1] * 1

        nodes = int(scn["num_nodes"])
        self.va = [Server(xi(scn["va_cost"]), f"node{i % nodes}") for i in range(scn["num_va"])]
        self.cr = [Server(xi(scn["cr_cost"]), f"node{i % nodes}") for i in range(scn["num_cr"])]
        self.xi_fc = xi(scn["fc_cost"])
        self.d_fv = hop("edge", "node", float(net["frame_bytes"]))
        self.d_vc = {(a, b): hop(self.va[a].node, self.cr[b].node, float(net["frame_bytes"]))
                     for a in range(len(self.va)) for b in range(len(self.cr))}
        self.d_cu = hop(self.cr[0].node, "head", float(net["detection_bytes"]))
        self.cr_rng = [np.random.default_rng(int(scn["seed"]) + 101) for _ in self.cr]
        self.cam_rng = np.random.default_rng(int(scn["seed"]) + 13)
        self.entity_emb = (self.cam_rng.normal(size=(self.dim,)).astype(np.float32)
                           if self.dim else None)

        self.now = 0.0
        self._heap: List[tuple] = []
        self._seq = 0
        self.queries = [Query(i, p) for i, p in enumerate(plans)]
        self.mask_of: Dict[int, int] = {}
        self.lit: set = set()          # cameras the control plane has switched on
        self.target: set = set()       # cameras TL last asked for
        self.pending: List[tuple] = []  # detections since the last TL tick
        self.g = dict(source_events=0, positives_generated=0, positives_completed=0,
                      detections_on_time=0, on_time=0, delayed=0, reid_matched=0,
                      reid_dispatches=0)
        self.g_latencies: List[tuple] = []
        self.g_timeline: List[tuple] = []
        self.gallery: List[bytes] = []
        for q in self.queries:
            if q.ttl_s is not None:
                self.at(max(q.submit_at, 0.0) + q.ttl_s, self.expire, q)
            if q.submit_at <= 0.0:
                self.activate(q, at_start=True)
            else:
                self.at(q.submit_at, self.activate, q)
        self.at(0.0, self.frame_tick)
        self.at(self.tl_period, self.tl_tick)

    # ---- the event loop ------------------------------------------------ #
    def at(self, t: float, fn, *args) -> None:
        self._seq += 1
        heapq.heappush(self._heap, (self.rnd(max(t, self.now)), self._seq, fn, args))

    def run_until(self, t: float) -> "Reference":
        t = min(t, self.horizon)
        while self._heap and self._heap[0][0] <= t:
            self.now, _, fn, args = heapq.heappop(self._heap)
            fn(*args)
        return self

    # ---- queries --------------------------------------------------------- #
    def activate(self, q: Query, at_start: bool = False) -> None:
        q.last_cam, q.last_t = self.w.nearest_camera(self.now), self.now
        q.requested = self.spotlight(q)
        if self.dim:
            q.emb = (self.entity_emb if q.embedding_seed is None else
                     np.random.default_rng(q.embedding_seed).normal(size=(self.dim,))
                     .astype(np.float32))
        q.state = "scoped"
        if at_start:
            for cam in q.requested:
                self.apply(q, cam, True)
                self.lit.add(cam)
        else:
            for cam in sorted(q.requested):
                self.at(self.now + self.lat, self.apply, q, cam, True)
            for cam in sorted(q.requested - self.target):
                self.at(self.now + self.lat, self.switch, cam, True)
        self.target |= q.requested

    def expire(self, q: Query) -> None:
        if q.state in ("expired", "cancelled", "found"):
            return
        live, q.state, q.ended_at = q.live, "expired", self.now
        if not live:
            return
        for cam in sorted(q.requested):
            self.at(self.now + self.lat, self.apply, q, cam, False)
        q.requested = set()
        union = set().union(*(p.requested for p in self.queries if p.live))
        for cam in sorted(self.target - union):
            self.at(self.now + self.lat, self.switch, cam, False)
        self.target = union

    def apply(self, q: Query, cam: int, on: bool) -> None:
        if on:
            if q.state in ("expired", "cancelled"):
                return
            q.applied.add(cam)
            self.mask_of[cam] = self.mask_of.get(cam, 0) | q.bit
        else:
            q.applied.discard(cam)
            self.mask_of[cam] = self.mask_of.get(cam, 0) & ~q.bit

    def switch(self, cam: int, on: bool) -> None:
        (self.lit.add if on else self.lit.discard)(cam)

    def spotlight(self, q: Query) -> set:
        radius = self.min_radius + q.speed * max(self.now - q.last_t, 0.0)
        return {int(c) for c in np.nonzero(self.w.road_distance(q.last_cam) <= radius)[0]}

    # ---- TL ------------------------------------------------------------- #
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
                self.at(self.now + self.lat, self.apply, q, cam, True)
            for cam in sorted(q.requested - new):
                self.at(self.now + self.lat, self.apply, q, cam, False)
            q.requested = new
            union |= new
        self.g_timeline.append((self.now, len(union)))
        for cam in sorted(union - self.target):
            self.at(self.now + self.lat, self.switch, cam, True)
        for cam in sorted(self.target - union):
            self.at(self.now + self.lat, self.switch, cam, False)
        self.target = union
        if self.now + self.tl_period <= self.duration:
            self.at(self.now + self.tl_period, self.tl_tick)

    # ---- frames through FC -> VA -> CR -> sink --------------------------- #
    def frame_tick(self) -> None:
        t = self.now
        if self.lit:
            pos = self.w.walk.position(t)
            groups: Dict[int, list] = {}
            for cam in sorted(self.lit):
                has = self.w.visible(cam, t, pos)
                emb = None
                if self.dim:
                    emb = ((self.entity_emb + self.cam_rng.normal(scale=0.1, size=(self.dim,)))
                           if has else self.cam_rng.normal(size=(self.dim,))).astype(np.float32)
                mask = self.mask_of.get(cam, 0)
                if not mask:
                    continue
                self.g["source_events"] += 1
                self.g["positives_generated"] += has
                for q in self.queries:
                    if mask & q.bit:
                        q.n["sourced"] += 1
                        q.n["positives_generated"] += has
                groups.setdefault(cam % len(self.va), []).append(
                    dict(cam=cam, t=t, has=has, emb=emb, mask=mask))
            for lane, frames in groups.items():
                self.at((t + self.xi_fc) + self.d_fv, self.deliver, lane, frames)
        if t + self.period <= self.duration:
            self.at(t + self.period, self.frame_tick)

    def deliver(self, lane: int, frames: list) -> None:
        for f in frames:
            self.arrive(self.va[lane], f, self.va_done)

    def arrive(self, srv: Server, f: dict, done) -> None:
        srv.arrived += 1
        if not srv.queue and self.now >= srv.free_at:
            srv.executed += 1
            self.serve(srv, f, done)
            return
        srv.queue.append(f)
        if not srv.waking:
            srv.waking = True
            self.at(srv.free_at, self.wake, srv, done)

    def wake(self, srv: Server, done) -> None:
        srv.waking = False
        self.serve(srv, srv.queue.popleft(), done)
        self.at(srv.free_at, self.finish, srv)
        if srv.queue:
            srv.waking = True
            self.at(srv.free_at, self.wake, srv, done)

    def finish(self, srv: Server) -> None:
        srv.executed += 1

    def serve(self, srv: Server, f: dict, done) -> None:
        srv.free_at = self.rnd(self.now + srv.xi)
        done(srv, f)

    def va_done(self, srv: Server, f: dict) -> None:
        block = [q for q in self.queries if q.live and q.emb is not None]
        if f["emb"] is not None and block:
            self.g["reid_dispatches"] += 1
            self.gallery.append(f["emb"].tobytes())
            g = f["emb"].astype(np.float64)
            g = g / max(np.linalg.norm(g), 1e-6)
            hit = False
            for q in block:
                if f["mask"] & q.bit:
                    e = q.emb.astype(np.float64)
                    if float(g @ (e / max(np.linalg.norm(e), 1e-6))) >= self.thr:
                        q.n["reid_matched"] += 1
                        hit = True
            self.g["reid_matched"] += hit
        lane = f["cam"] % len(self.cr)
        va = self.va.index(srv)
        self.at(srv.free_at + self.d_vc[(va, lane)], self.arrive, self.cr[lane], f, self.cr_done)

    def cr_done(self, srv: Server, f: dict) -> None:
        rng = self.cr_rng[self.cr.index(srv)]
        f["positive"] = bool(f["has"]) and float(rng.uniform()) <= self.p_tp
        self.at(srv.free_at + self.d_cu, self.sink, f)

    def sink(self, f: dict) -> None:
        u = self.rnd(self.now - f["t"])
        ok = u <= self.gamma
        self.g_latencies.append((self.now, u))
        self.g["on_time" if ok else "delayed"] += 1
        if f["positive"]:
            self.g["positives_completed"] += 1
            self.g["detections_on_time"] += ok
        self.pending.append((f["cam"], f["positive"], f["t"], f["mask"]))
        for q in self.queries:
            if not f["mask"] & q.bit:
                continue
            if not q.live:
                q.n["orphan_completed"] += 1
                continue
            q.n["completed"] += 1
            q.latencies.append((self.now, u))
            q.n["on_time" if ok else "delayed"] += 1
            if f["positive"]:
                q.n["positives_completed"] += 1
                q.n["detections_on_time"] += ok
                if q.state == "scoped":
                    q.state, q.found_at = "found", self.now
        return None

    # ---- the books ------------------------------------------------------- #
    def observe(self) -> Dict[str, Any]:
        """Books as of the simulated time reached: ``exact`` must equal the
        platform's field for field, ``timed`` within the latency limit.
        Every frame a server executed was a batch of its own, and nothing is
        dropped."""
        modules = {}
        for name, servers in (("VA", self.va), ("CR", self.cr)):
            done = sum(s.executed for s in servers)
            modules[name] = dict(NO_DROPS, arrived=sum(s.arrived for s in servers),
                                 executed=done, batches=done)
        exact = {"modules": modules, "per": {}, "global": dict(self.g),
                 "gallery": sorted(self.gallery), "timeline": list(self.g_timeline)}
        timed = {"global": sorted(self.g_latencies), "per": {}}
        for q in self.queries:
            exact["per"][q.qid] = dict(q.n, dropped=0, orphan_dropped=0, dp=dict(NO_DROPS),
                                       state=q.state, ended_at=q.ended_at,
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
#: simulates (``m_max``, ``epsilon_max`` and ``static_batch`` with drops off
#: and dynamic batching; ``tl``, which the query plans override).
KNOWN = {"num_cameras", "duration_s", "fps", "entity_speed_mps", "fov_radius_m", "seed",
         "road_vertices", "gamma", "epsilon_max", "tl", "tl_update_period",
         "tl_min_radius_m", "batching", "static_batch", "m_max", "drops_enabled",
         "avoid_drop_positives", "num_va", "num_cr", "num_nodes", "fc_cost", "va_cost",
         "cr_cost", "p_true_positive", "embed_dim", "reid_threshold"}


def refuses(config: Dict[str, Any], plans: Sequence[Dict]) -> Optional[str]:
    """Why this simulator cannot judge ``config`` under ``plans``, or None."""
    scn = config["scenario"]
    why = []
    tls = sorted({p["tl"] for p in plans} - {"wbfs"})
    if tls:
        why.append(f"queries with TL {tls} (it simulates TL-WBFS only)")
    if scn.get("drops_enabled"):
        why.append("drops_enabled: true (it simulates drops off)")
    if scn.get("avoid_drop_positives"):
        why.append("avoid_drop_positives: true (it simulates no drop path)")
    if scn.get("batching", "dynamic") != "dynamic":
        why.append(f"batching {scn['batching']!r} (it simulates dynamic batching, "
                   f"a batch of one with drops off)")
    unknown = sorted(set(scn) - KNOWN)
    if unknown:
        why.append(f"scenario keys it does not simulate: {unknown}")
    return "refsim refuses " + "; ".join(why) if why else None


def books(config: Dict[str, Any], plans: Sequence[Dict], cuts: Sequence[float], *,
          time32: bool = False) -> Dict[float, Dict[str, Any]]:
    """The books of one replay at each simulated time of ``cuts`` (the
    horizon for a finished replay)."""
    reason = refuses(config, plans)
    if reason is not None:
        raise ValueError(reason)
    sim = Reference(config, World(config["scenario"]), plans, time32=time32)
    return {t: sim.run_until(t).observe() for t in sorted(set(cuts))}
