"""Quickstart: compose App 1 (paper Table 1) and execute it via the app
compiler.

    PYTHONPATH=src python examples/quickstart.py

Composes the domain-specific dataflow — FC (isActive) -> VA (detector) ->
CR (re-id) -> TL (WBFS spotlight) — and runs the composed ``TrackingApp``
itself on the 1000-camera discrete-event platform:
``repro.core.compile.compile_app`` lowers the app + world + deployment onto
the Task DAG and ``TrackingScenario`` drives it.  The tuning-triangle claim
to check: with dynamic batching, zero events miss the gamma deadline.
"""

import sys

sys.path.insert(0, "src")

from repro.core.compile import DeploymentSpec, linear_xi
from repro.core.dataflow import ModuleSpec, TrackingApp, fc_is_active, make_cr, make_va
from repro.core.tracking import TLWBFS
from repro.sim import ScenarioConfig, TrackingScenario, WorldKey, get_world


def hog_detector(frames, query):
    """Stand-in for OpenCV HoG: every frame yields person candidates."""
    return [[(0, 0, 64, 128)] for _ in frames]


def openreid_matcher(crops, query):
    """Stand-in for the OpenReid DNN verdicts (crops arrive as
    ``(frame, boxes)`` pairs from the VA stage)."""
    return [bool(getattr(c[0], "has_entity", False)) for c in crops]


def main() -> None:
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    # --- the workload: 1000 cameras, 300 s, the paper's entity walk ------ #
    cfg = ScenarioConfig(num_cameras=1000, duration_s=300.0)
    world = get_world(WorldKey.from_config(cfg))

    # --- compose App 1 (pure DSL; Table 1 row 1) ------------------------- #
    app = TrackingApp(
        name="app1-missing-person",
        fc=fc_is_active,
        va=make_va(hog_detector),
        cr=make_cr(openreid_matcher),
        tl=TLWBFS(world.road, world.cameras.camera_vertices, entity_speed=4.0),
        specs={
            "FC": ModuleSpec(xi=linear_xi(0.0002, 0.0008), resource_tier="edge"),
            "VA": ModuleSpec(instances=10, resource_tier="fog",
                             batching="dynamic", m_max=25,
                             xi=linear_xi(0.020, 0.010)),
            "CR": ModuleSpec(instances=10, resource_tier="cloud",
                             batching="dynamic", m_max=25,
                             xi=linear_xi(0.067, 0.053)),
        },
        gamma=15.0,
    )
    print(f"Composed {app.name}: gamma={app.gamma}s, "
          f"VA x{app.spec('VA').instances}, CR x{app.spec('CR').instances}")

    # --- compile + run it on the discrete-event platform ----------------- #
    # TrackingScenario lowers the app through compile_app and drives the
    # compiled pipeline; the DeploymentSpec holds the platform-side knobs.
    scenario = TrackingScenario(cfg, app=app, deployment=DeploymentSpec(num_nodes=10))
    res = scenario.run()
    s = res.summary()
    print("\nScenario summary:")
    for k, v in s.items():
        print(f"  {k:22s} {v}")
    assert s["delayed"] == 0, "dynamic batching should meet every deadline"
    print("\nOK: all events within gamma; spotlight peaked at "
          f"{s['peak_active']} of 1000 cameras.")

    # --- same app under dynamism: a Fig.-9-style bandwidth collapse ------ #
    # A DynamismSpec attaches to the workload config; the platform composes
    # the perturbation onto the network model, samples per-task telemetry
    # on a 5 s cadence, and scores tracking quality against the ground
    # truth.  Drops are enabled so the completion-budget protocol is live.
    from repro.sim import BandwidthCollapse, DynamismSpec

    perturbed = ScenarioConfig(
        num_cameras=300, duration_s=150.0, batching="dynamic",
        drops_enabled=True, avoid_drop_positives=True,
        dynamism=DynamismSpec((BandwidthCollapse(50.0, 90.0, 2e-5),)),
    )
    res2 = TrackingScenario(perturbed).run()
    trace = res2.trace
    rec = trace.budget_recovery("CR")
    q = res2.quality
    print("\nDynamism: 1 Gbps link collapses over t=[50,90)s ...")
    print(f"  CR budget: pre={rec['pre']:.1f}s  post={rec['post']:.1f}s "
          f"(recovery {rec['recovery']:.2f}x via {res2.summary()['probes']} probes)")
    print(f"  dropped {res2.dropped_fraction:.0%} of frames, yet track "
          f"recall={q['track_recall']:.2f} precision={q['track_precision']:.2f}")
    assert rec["recovery"] >= 0.9, "dynamic batching should recover its budget"
    print("OK: budget recovered after the collapse.")

    # --- multi-query tenancy: two users, one shared pipeline ------------- #
    # The platform serves a *set* of concurrent tracking queries through
    # ONE pipeline: each sourced frame is tagged with the live queries
    # interested in its camera, the active set is the union of the queries'
    # spotlights, and per-query summaries are split back out at the sink.
    # Query 1 is cancelled mid-run; its cameras drop out of the union and
    # anything still in flight is orphan-accounted, never attributed.
    from repro.query import MultiQueryScenario, QuerySpec

    mq_cfg = ScenarioConfig(num_cameras=300, duration_s=150.0)
    res3 = MultiQueryScenario(
        mq_cfg,
        [
            QuerySpec(),                          # user A: track from t=0
            QuerySpec(submit_at=20.0, cancel_at=90.0),  # user B: cancels
        ],
    ).run()
    print("\nMulti-query: two queries, one pipeline ...")
    for qid, st in sorted(res3.registry.states.items()):
        s_q = res3.per_query_summary(qid)
        print(f"  query {qid}: state={st.state:9s} events={s_q['source_events']}"
              f" positives={s_q['positives_completed']}"
              f" median_lat={s_q['median_latency_s']}s")
    g = res3.summary()
    print(f"  shared pipeline sourced {g['source_events']} events for "
          f"{g['per_query_sourced_sum']} per-query deliveries "
          f"(union peak {g['union_peak_active']} cameras)")
    assert res3.states[0] == "found" and res3.states[1] == "cancelled"
    assert res3.registry.reconcile()[1]["unaccounted"] == 0
    print("OK: multi-query tenancy — cancelled mid-run, books balanced.")

    # --- fault tolerance: crash a host, restore from the journal --------- #
    # A HostCrash kills node0 for 20 s mid-run: its queued events are lost
    # (charged as the dp_fault drop class), blocked sends retry with seeded
    # backoff, and the books still reconcile exactly.  The serving driver
    # journals the event stream + periodic snapshots; after the driver
    # itself is killed at t=100, a fresh build replays to the last snapshot
    # (bit-verified) and continues — producing per-query summaries
    # bit-identical to a run that was never interrupted.
    from repro.serving.journal import Journal
    from repro.sim import HostCrash

    fault_cfg = lambda: ScenarioConfig(
        num_cameras=100, duration_s=120.0,
        dynamism=DynamismSpec((HostCrash(("node0",), t_start=60.0, outage_s=20.0),)),
    )
    ref = MultiQueryScenario(fault_cfg(), 2, journal=Journal(snapshot_period_s=30.0))
    ref_res = ref.run()

    crashed = MultiQueryScenario(fault_cfg(), 2, journal=Journal(snapshot_period_s=30.0))
    crashed.run_until(100.0)  # the driver dies here; only its journal survives
    wal = crashed.journal

    recovered = MultiQueryScenario(fault_cfg(), 2, journal=Journal(snapshot_period_s=30.0))
    recovered.restore(wal)  # replay to t=90, bit-verify the frontier
    rec_res = recovered.run()

    print("\nFault tolerance: node0 crashes over t=[60,80)s, driver killed at t=100 ...")
    s_ref = ref_res.per_query_summary(0)
    print(f"  lost {ref_res.per_query[0].drops_by_task.get('dp_fault', 0)} events to "
          f"the crash; {s_ref['source_events']} sourced == "
          f"{s_ref['on_time'] + s_ref['delayed']} completed + {s_ref['dropped']} dropped")
    assert all(
        rec_res.per_query_summary(q) == ref_res.per_query_summary(q)
        for q in ref_res.per_query
    )
    assert recovered.journal.digest() == ref.journal.digest()
    print("OK: crash-and-restore — recovered run bit-identical to uninterrupted.")


if __name__ == "__main__":
    main()
