"""The four tracking applications of paper Table 1, composed in the DSL and
**executed end-to-end** through the app compiler.

    PYTHONPATH=src python examples/apps.py

Demonstrates the programming model (paper §2.3): each app is a handful of
lines — only the module logics change, the dataflow is fixed — and a
composed :class:`TrackingApp` is the platform's executable unit.  The main
program runs all four apps through ``SweepRunner``: each grid case pairs an
app *factory* with a workload, the runner builds the app against the
shared world and ``repro.core.compile.compile_app`` lowers it onto the
discrete-event pipeline (App 2 exercising the QF query-fusion feedback
edge, App 4 the real JAX re-id towers through the bucket-batched kernel
dispatch plane).

Apps 1-3 are pure discrete-event simulation and run in a fork pool where
available.  App 4 dispatches to the JAX device, which takes one process, so
it runs afterwards in this process.
"""

import sys

sys.path.insert(0, "src")

from dataclasses import replace

from repro.core.compile import DeploymentSpec, linear_xi
from repro.core.dataflow import (
    ModuleSpec,
    TrackingApp,
    fc_frame_rate,
    fc_is_active,
    make_cr,
    make_va,
)
from repro.core.tracking import TLBFS, TLProbabilistic, TLWBFS
from repro.sim import AppCase, ScenarioConfig, SweepRunner

# One workload for the whole grid: a 300-camera / 60 s slice of the paper's
# setup (the benchmarks run the full 1000-camera grids).  App 4 adds real
# 128-d frame embeddings so its towers have tensors to chew on.
WORKLOAD = ScenarioConfig(num_cameras=300, duration_s=60.0, seed=0)
EMBED_WORKLOAD = replace(WORKLOAD, embed_dim=128)

# Paper cost models: VA ~30 ms/frame streaming, CR ~120 ms/event (App 1),
# App 2's better CR DNN ~63% slower, App 3's YOLO heavier than HoG.
_FC_COST = (0.0002, 0.0008)
_VA_COST = (0.020, 0.010)
_CR_COST = (0.067, 0.053)


def _specs(batching="dynamic", va_scale=1.0, cr_scale=1.0):
    return {
        "FC": ModuleSpec(xi=linear_xi(*_FC_COST), resource_tier="edge"),
        "VA": ModuleSpec(
            instances=10, resource_tier="fog", batching=batching, m_max=25,
            xi=linear_xi(_VA_COST[0] * va_scale, _VA_COST[1] * va_scale),
        ),
        "CR": ModuleSpec(
            instances=10, resource_tier="cloud", batching=batching, m_max=25,
            xi=linear_xi(_CR_COST[0] * cr_scale, _CR_COST[1] * cr_scale),
        ),
    }


def _frame_of(value):
    """VA emits ``(frame, boxes)`` pairs; CR crops unwrap to the frame."""
    return value[0] if isinstance(value, tuple) else value


# --------------------------------------------------------------------- #
# The four apps (Table 1).  Each builder takes the world geometry the    #
# app will run over; the analytics are stand-ins except App 4's real     #
# JAX towers.                                                            #
# --------------------------------------------------------------------- #
def build_app1(road, cameras, batching="dynamic"):
    """App 1: missing person — HoG + OpenReid stand-ins + WBFS spotlight."""
    hog = lambda frames, q: [[(0, 0, 64, 128)] for _ in frames]           # [20]
    person_reid = lambda crops, q: [
        bool(getattr(_frame_of(c), "has_entity", False)) for c in crops   # [2]
    ]
    return TrackingApp(
        name="app1",
        fc=fc_is_active,
        va=make_va(hog),
        cr=make_cr(person_reid),
        tl=TLWBFS(road, cameras, entity_speed=4.0),
        specs=_specs(batching),
    )


def build_app2(road, cameras, batching="dynamic"):
    """App 2: better CR DNN + query fusion + plain BFS.  QF fuses every
    confirmed sighting into the entity query (stand-in for the RNN query
    refresher [42]); the platform pushes each fused query to the VA/CR
    states over the control network."""
    hog = lambda frames, q: [[(0, 0, 64, 128)] for _ in frames]
    person_reid_v2 = lambda crops, q: [
        bool(getattr(_frame_of(c), "has_entity", False)) for c in crops   # [8]
    ]

    def qf_fuse(detections, state):
        fused = state.get("fused_hits", 0) + len(detections)
        state["fused_hits"] = fused
        return ("query", fused)  # a new (refined) query object per fusion

    return TrackingApp(
        name="app2",
        fc=fc_is_active,
        va=make_va(hog),
        cr=make_cr(person_reid_v2),
        tl=TLBFS(road, cameras, entity_speed=4.0, fixed_edge_length_m=84.5),
        qf=qf_fuse,
        specs=_specs(batching, cr_scale=1.63),
    )


def build_app3(road, cameras, batching="dynamic"):
    """App 3: stolen vehicle — frame-rate FC, YOLO + car re-id stand-ins,
    speed-aware WBFS (~50 km/h car)."""
    yolo_cars = lambda frames, q: [[(0, 0, 96, 64)] for _ in frames]      # [47]
    car_reid = lambda crops, q: [
        bool(getattr(_frame_of(c), "has_entity", False)) for c in crops   # [53]
    ]
    return TrackingApp(
        name="app3",
        fc=fc_frame_rate,
        va=make_va(yolo_cars),
        cr=make_cr(car_reid),
        tl=TLWBFS(road, cameras, entity_speed=14.0),
        specs=_specs(batching, va_scale=1.5),
    )


def build_app4(road, cameras, batching="dynamic", entity_embedding=None):
    """App 4: small/large re-id tower pair + probabilistic TL — the real
    JAX towers, with gallery scoring routed through the bucket-batched
    kernel dispatch plane (``repro.kernels.dispatch``).

    ``entity_embedding`` is the tracked entity's raw 128-d feature (the
    simulator's camera network exposes it when the workload carries
    ``embed_dim=128``); the entity query holds its small/large tower
    embeddings.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.serving import embed_frames, init_reid_tower

    small_tower = init_reid_tower(jax.random.PRNGKey(0), d_in=128, d_hidden=128, d_embed=32)
    large_tower = init_reid_tower(jax.random.PRNGKey(1), d_in=128, d_hidden=512, d_embed=64, depth=4)

    if entity_embedding is None:
        entity_embedding = np.zeros(128, np.float32)
    query = {
        "small": np.asarray(embed_frames(small_tower, jnp.asarray(entity_embedding)[None, :])),
        "large": np.asarray(embed_frames(large_tower, jnp.asarray(entity_embedding)[None, :])),
    }

    def _features(values):
        feats = []
        for v in values:
            frame = _frame_of(v)
            if isinstance(frame, np.ndarray):  # raw feature vector
                feats.append(np.asarray(frame, np.float32))
                continue
            emb = getattr(frame, "embedding", None)
            feats.append(np.zeros(128, np.float32) if emb is None else emb)
        return np.stack(feats)

    def _query(q, tower):
        # The compiled app carries the small/large tower query pair; callers
        # poking the logic directly may pass a bare embedded query.
        return q[tower] if isinstance(q, dict) else np.asarray(q)

    def reid_small(frames, q):  # VA: cheap tower filters candidates
        embs = np.asarray(embed_frames(small_tower, jnp.asarray(_features(frames))))
        _, _, hits = dispatch.reid_match(embs, _query(q, "small"), threshold=0.3)
        return [[(0, 0, 64, 128)] if bool(h) else [] for h in np.asarray(hits)]

    def reid_large(crops, q):  # CR: accurate tower confirms
        embs = np.asarray(embed_frames(large_tower, jnp.asarray(_features(crops))))
        _, _, hits = dispatch.reid_match(embs, _query(q, "large"), threshold=0.7)
        return [bool(h) for h in np.asarray(hits)]

    return TrackingApp(
        name="app4",
        fc=fc_is_active,
        va=make_va(reid_small),
        cr=make_cr(reid_large),
        tl=TLProbabilistic(road, cameras, entity_speed=4.0, coverage=0.9),
        entity_query=query,
        specs=_specs(batching),
    )


_BUILDERS = {"app1": build_app1, "app2": build_app2, "app3": build_app3, "app4": build_app4}


def app_factory(name, batching="dynamic"):
    """A sweep-grid factory ``(world, cameras) -> TrackingApp``: the app is
    built against the case's world geometry inside the worker process."""
    build = _BUILDERS[name]

    def factory(world, cameras):
        kw = {}
        if name == "app4":
            kw["entity_embedding"] = getattr(cameras, "entity_embedding", None)
        return build(world.road, cameras.camera_vertices, batching=batching, **kw)

    return factory


def table1_grid(batching="dynamic"):
    """All four Table-1 apps as one ``SweepRunner`` grid."""
    grid = []
    for name in ("app1", "app2", "app3"):
        grid.append(
            (name, AppCase(app=app_factory(name, batching), workload=WORKLOAD,
                           deployment=DeploymentSpec()))
        )
    grid.append(
        ("app4", AppCase(app=app_factory("app4", batching), workload=EMBED_WORKLOAD,
                         deployment=DeploymentSpec(), needs_jax=True))
    )
    return grid


def build_apps(road=None, cameras=None):
    """All four apps composed against one (small, display-only) world —
    the DSL-conciseness exhibit (paper §2.3)."""
    if road is None:
        from repro.core.roadnet import make_road_network

        road = make_road_network(seed=0)
    if cameras is None:
        cameras = {i: i for i in range(min(1000, road.num_vertices))}
    return [
        build_app1(road, cameras),
        build_app2(road, cameras),
        build_app3(road, cameras),
        build_app4(road, cameras),
    ]


def main() -> None:
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    # ---- execute: the composed apps ARE the runnable artifact ---------- #
    # The pure-DES apps fork first, while no JAX backend exists in this
    # process; App 4 then brings JAX up here.
    grid = table1_grid("dynamic")
    des_grid = [(name, case) for name, case in grid if not case.needs_jax]
    jax_grid = [(name, case) for name, case in grid if case.needs_jax]
    mode = "fork" if SweepRunner.fork_available() else "serial"
    print(f"Running the four Table-1 apps end-to-end (SweepRunner, {mode})...\n")
    for res in (SweepRunner(mode=mode).run(des_grid),
                SweepRunner(mode="serial").run(jax_grid)):
        for rec in res.records:
            s = rec.summary
            print(
                f"  {rec.name}: events={s['source_events']} on_time={s['on_time']} "
                f"delayed={s['delayed']} peak_active={s['peak_active']} "
                f"positives={s['positives_completed']}/{s['positives_generated']} "
                f"({rec.run_s:.2f}s run)"
            )
        print(f"\nSweep: mode={res.mode} workers={res.workers} wall={res.wall_s:.2f}s")

    # ---- compose: the DSL-conciseness exhibit -------------------------- #
    apps = build_apps()
    print(f"\nComposed {len(apps)} tracking applications (paper Table 1):\n")
    for app in apps:
        tl_name = type(app.tl).__name__
        print(
            f"  {app.name}: FC={app.fc.__name__} TL={tl_name} "
            f"QF={'yes' if app.qf else '—'} gamma={app.gamma}s "
            f"(VA x{app.spec('VA').instances} on {app.spec('VA').resource_tier}, "
            f"CR x{app.spec('CR').instances} on {app.spec('CR').resource_tier})"
        )
    # Exercise App 4's real JAX towers once more, standalone.
    import numpy as np

    frames = np.random.default_rng(0).normal(size=(6, 128)).astype(np.float32)

    class _F:  # minimal frame stand-in with a feature vector
        def __init__(self, emb):
            self.embedding = emb

    boxes = apps[3].va(0, [_F(f) for f in frames], {"entity_query": apps[3].entity_query})
    print(f"\nApp 4 small-tower VA scored {len(boxes)} frames "
          f"({sum(1 for _, b in boxes if b)} candidates) — JAX end to end.")


if __name__ == "__main__":
    main()
