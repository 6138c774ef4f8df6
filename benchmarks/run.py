"""Benchmark harness: every paper figure grid runs as ONE sweep through the
shared-world :class:`repro.sim.SweepRunner` (worlds built once per key,
configs executed concurrently via a fork pool where available), plus kernel
timings and the roofline table.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run                    # everything
    PYTHONPATH=src python -m benchmarks.run --only fig567
    PYTHONPATH=src python -m benchmarks.run --only fig567 --mode serial
    PYTHONPATH=src python -m benchmarks.run --only pipeline --json BENCH_pipeline.json
    PYTHONPATH=src python -m benchmarks.run --only pipeline --smoke          # CI-fast
    PYTHONPATH=src python -m benchmarks.run --only pipeline --smoke \\
        --compare BENCH_pipeline.json                          # regression gate

``--json PATH`` writes the machine-readable records ``{bench, case,
us_per_event, derived, run_s, build_s, xfer_s, mode}`` accumulated by the
selected benchmarks (the checked-in ``BENCH_pipeline.json`` holds the
``pipeline`` records in both full and smoke modes).  ``us_per_event`` is
computed from ``run()`` wall-time only; construction is reported separately
as ``build_s``, and device engines split the host<->device transfer wall
out of ``run_s`` into ``xfer_s`` (``null`` for families that do no device
transfer, and backfilled as ``null`` when comparing against baselines
recorded before the column existed).

``--compare PATH`` re-times the comparable benchmark families recorded in
PATH (pipeline, the fused multi-query cases, the mega-step engine runs,
and the journaled fault-crash runs, matching the current ``--smoke`` mode)
and exits non-zero when any ``us_per_event`` regressed by more than
``--compare-tolerance`` (default 35%).  Families absent from a
frozen baseline are tolerated, so old baselines keep gating after new
benchmark families land.

``--mode`` selects the sweep execution: ``auto`` (fork pool when available),
``fork``, ``serial`` (shared worlds, one case at a time), or ``cold``
(serial AND world/road caches cleared before every case — the faithful
"rebuild everything per config" sequential baseline).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.clock import monotonic
from repro.sim import (
    BandwidthCollapse,
    ComputeSlowdown,
    DynamismSpec,
    ScenarioConfig,
    SweepResult,
    SweepRunner,
)

from .scenarios import RECORDS, record, record_case

SEP = "-" * 78

# --------------------------------------------------------------------- #
# Frozen baselines                                                       #
# --------------------------------------------------------------------- #

# Per-event cost measured at the seed commit (9931f3f, pure-Python per-event
# runtime) on the same container; see PERF.md for methodology.
SEED_US_PER_EVENT = {
    "Base_SB-20_200c": 107.5,
    "BFS_DB-25_1000c": 284.1,
}

# Whole-grid sequential wall-clock measured at commit 26d2c35 (the PR-1
# harness: one scenario at a time, construction+run timed together) on the
# same container.  The sweep records report their speedup against these.
SEED_SEQ_WALL_S = {
    "fig567": 2.6,
    "fig9": 1.1,
    "fig10": 3.1,
    "fig11": 4.2,
    "fig12": 2.5,
    "fig13": 11.3,
}

# --------------------------------------------------------------------- #
# Paper-figure grids (each runs as one sweep)                            #
# --------------------------------------------------------------------- #

_CR2 = (0.067 * 1.63, 0.053 * 1.63)  # App 2: CR ~63% slower per frame


def _fig9_bandwidth(t: float) -> float:
    """Fig. 9: 1 Gbps -> 30 Mbps at t = 300 s."""
    return 1.0 if t < 300.0 else 0.03


GRIDS: Dict[str, Dict] = {
    "pipeline": dict(
        title="Pipeline hot path — reference scenarios",
        base=dict(tl_peak_speed=4.0),
        cases=[
            ("Base_SB-20_200c", dict(tl="base", num_cameras=200, batching="static", static_batch=20)),
            ("BFS_DB-25_1000c", dict(tl="bfs", batching="dynamic", m_max=25)),
        ],
    ),
    "fig567": dict(
        title="Fig 5/6/7 — batching strategies, TL-BFS, 1000 cameras",
        base=dict(tl="bfs"),
        cases=[
            ("SB-1_es4", dict(batching="static", static_batch=1, tl_peak_speed=4.0)),
            ("SB-20_es4", dict(batching="static", static_batch=20, tl_peak_speed=4.0)),
            ("DB-25_es4", dict(batching="dynamic", m_max=25, tl_peak_speed=4.0)),
            ("NOB-25_es4", dict(batching="nob", m_max=25, tl_peak_speed=4.0)),
            ("SB-1_es6", dict(batching="static", static_batch=1, tl_peak_speed=6.0)),
            ("SB-20_es6", dict(batching="static", static_batch=20, tl_peak_speed=6.0)),
            ("DB-25_es6", dict(batching="dynamic", m_max=25, tl_peak_speed=6.0)),
        ],
    ),
    "fig10": dict(
        title="Fig 10 — tracking logic: active-set scalability",
        base=dict(tl_peak_speed=4.0),
        cases=[
            ("Base_SB-20_100c", dict(tl="base", num_cameras=100, batching="static", static_batch=20)),
            ("Base_SB-20_200c", dict(tl="base", num_cameras=200, batching="static", static_batch=20)),
            ("BFS_SB-1_1000c", dict(tl="bfs", batching="static", static_batch=1)),
            ("WBFS_SB-1_1000c", dict(tl="wbfs", batching="static", static_batch=1)),
            ("BFS_DB-25_1000c", dict(tl="bfs", batching="dynamic", m_max=25)),
            ("WBFS_DB-25_1000c", dict(tl="wbfs", batching="dynamic", m_max=25)),
            ("Prob_DB-25_1000c", dict(tl="prob", batching="dynamic", m_max=25)),
        ],
    ),
    "fig11": dict(
        title="Fig 11 — drops under overload (es=7, constrained 5 VA + 5 CR)",
        base=dict(tl="bfs", tl_peak_speed=7.0, batching="dynamic", m_max=25, num_va=5, num_cr=5),
        cases=[
            ("es7_nodrop", dict(drops_enabled=False)),
            ("es7_drops", dict(drops_enabled=True, avoid_drop_positives=True)),
        ],
    ),
    "fig9": dict(
        title="Fig 9 — adapting to a 1Gbps->30Mbps bandwidth drop at t=300s",
        base=dict(tl="bfs", tl_peak_speed=4.0, bandwidth_schedule=_fig9_bandwidth),
        cases=[
            ("DB-25_bwdrop", dict(batching="dynamic", m_max=25)),
            ("NOB-25_bwdrop", dict(batching="nob", m_max=25)),
        ],
    ),
    "fig12": dict(
        title="Fig 12 — App 2 (CR ~63% slower per frame)",
        base=dict(tl="bfs", cr_cost=_CR2),
        cases=[
            ("app2_SB-20_es4", dict(batching="static", static_batch=20, tl_peak_speed=4.0)),
            ("app2_DB-25_es4", dict(batching="dynamic", m_max=25, tl_peak_speed=4.0)),
            ("app2_DB-25_es6", dict(batching="dynamic", m_max=25, tl_peak_speed=6.0)),
            (
                "app2_DB-25_es6_drops",
                dict(batching="dynamic", m_max=25, tl_peak_speed=6.0,
                     drops_enabled=True, avoid_drop_positives=True),
            ),
            ("app2_WBFS_SB-20_es4", dict(tl="wbfs", batching="static", static_batch=20,
                                         tl_peak_speed=4.0)),
        ],
    ),
    "fig13": dict(
        title="Fig 13 — scale sweep (spotlight TL, dynamic batching)",
        base=dict(tl="bfs", tl_peak_speed=4.0, batching="dynamic", m_max=25, duration_s=60.0),
        cases=[
            (f"scale_{n}c_{fps:g}fps", dict(num_cameras=n, fps=fps))
            for n in (1000, 5000, 10000)
            for fps in (1.0, 5.0)
        ],
    ),
}


# The pipeline cases double as the --compare gate's case universe.
PIPELINE_CASES = GRIDS["pipeline"]["cases"]


def _make_grid(bench: str, smoke: bool) -> List[Tuple[str, ScenarioConfig]]:
    info = GRIDS[bench]
    grid = []
    for name, kw in info["cases"]:
        cfg = dict(num_cameras=1000, duration_s=600.0, seed=0)
        cfg.update(info.get("base", {}))
        cfg.update(kw)
        if smoke:
            cfg["duration_s"] = min(cfg["duration_s"], 60.0)
        grid.append((name, ScenarioConfig(**cfg)))
    return grid


def _runner(ctx) -> SweepRunner:
    if ctx.mode == "cold":
        return SweepRunner(mode="serial", share_worlds=False)
    return SweepRunner(mode=ctx.mode, max_workers=ctx.workers)


def _mode_label(ctx) -> str:
    return "smoke" if ctx.smoke else "full"


def _sweep_record(bench: str, res: SweepResult, ctx) -> None:
    total_events = sum(r.summary["source_events"] for r in res.records)
    seed_wall = SEED_SEQ_WALL_S.get(bench)
    speedup = f"{seed_wall / res.wall_s:.2f}" if (seed_wall and not ctx.smoke) else "n/a"
    derived = (
        f"wall_s={res.wall_s:.3f};mode={res.mode};workers={res.workers};"
        f"configs={len(res.records)};worlds_built={res.worlds_built};"
        f"world_build_s={res.world_build_s:.3f};"
        f"seed_seq_wall_s={seed_wall};speedup_vs_seed_seq={speedup}"
    )
    record(
        bench, "sweep", res.wall_s * 1e6 / max(total_events, 1), derived,
        run_s=round(res.wall_s, 4), build_s=round(res.world_build_s, 4),
        mode=_mode_label(ctx),
    )
    print(f"{bench}_sweep,{res.wall_s * 1e6 / max(total_events, 1):.1f},{derived}")


def _run_grid(bench: str, ctx) -> SweepResult:
    print(f"{SEP}\n# {GRIDS[bench]['title']}")
    res = _runner(ctx).run(_make_grid(bench, ctx.smoke))
    for rec in res.records:
        print(record_case(bench, rec, mode=_mode_label(ctx)))
    _sweep_record(bench, res, ctx)
    return res


# --------------------------------------------------------------------- #
# Pipeline hot-path benchmark (PERF.md): per-event wall-clock on the two  #
# reference scenarios vs the frozen seed-commit baseline (best of reps).  #
# --------------------------------------------------------------------- #
def _time_pipeline_cases(ctx, reps: int) -> Dict[str, "object"]:
    # Per-event timing is always taken serially (worlds still shared):
    # concurrent execution would measure CPU contention, and the --compare
    # gate must see numbers produced the same way as the recorded baseline
    # regardless of the --mode used for the throughput sweeps.
    grid = _make_grid("pipeline", ctx.smoke)
    runner = SweepRunner(mode="serial")
    best: Dict[str, object] = {}
    for _ in range(reps):
        res = runner.run(grid)
        for rec in res.records:
            prev = best.get(rec.name)
            if prev is None or rec.run_s < prev.run_s:
                best[rec.name] = rec
    return best


def bench_pipeline(ctx) -> None:
    reps = 2 if ctx.smoke else 3
    print(f"{SEP}\n# Pipeline hot path — us per source event vs seed baseline (best of {reps})")
    best = _time_pipeline_cases(ctx, reps)
    for name, _ in PIPELINE_CASES:
        rec = best[name]
        us = rec.us_per_event
        seed_us = SEED_US_PER_EVENT.get(name)
        speedup = f"{seed_us / us:.2f}" if (seed_us and not ctx.smoke) else "n/a"
        s = rec.summary
        record(
            "pipeline",
            name,
            us,
            f"seed_us_per_event={seed_us};speedup_x={speedup};"
            f"events={s['source_events']};median_lat_s={s['median_latency_s']};"
            f"delayed={s['delayed']};dropped={s['dropped']};peak_active={s['peak_active']};"
            f"build_s={rec.build_s:.3f}",
            run_s=round(rec.run_s, 4),
            build_s=round(rec.build_s, 4),
            mode=_mode_label(ctx),
        )
        print(f"pipeline_{name},{us:.1f},seed={seed_us};speedup={speedup}x")


# --------------------------------------------------------------------- #
# Regression gate: --compare BENCH_pipeline.json                          #
# --------------------------------------------------------------------- #
def _retime_pipeline(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    """case -> (us_per_event, run_s, build_s) for the pipeline family."""
    reps = 2 if ctx.smoke else 3
    best = _time_pipeline_cases(ctx, reps)
    return {
        name: (rec.us_per_event, rec.run_s, rec.build_s)
        for name, rec in best.items()
        if name in cases
    }


def _retime_queries(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    """Re-time the fused multi-query cases present in the baseline.

    Same timing discipline as the recording side (bench_queries): the world
    cache is warmed before the timed window — the baselines were recorded
    warm, so a cold first build would read as a spurious regression — and
    each case takes the best of two runs (the walls are small enough for
    container noise to matter)."""
    from repro.query import MultiQueryScenario
    from repro.sim import WorldKey, get_world

    cams, dur, ns = _queries_shape(ctx.smoke)
    cfg = _queries_cfg(cams, dur)
    get_world(WorldKey.from_config(cfg))
    out: Dict[str, Tuple[float, float, float]] = {}
    for n in ns:
        name = f"fused_N{n}"
        if name not in cases:
            continue
        for _ in range(2):
            t0 = monotonic()
            scenario = MultiQueryScenario(cfg, n)
            res = scenario.run()
            wall = monotonic() - t0
            events = max(res.result.source_events, 1)
            prev = out.get(name)
            if prev is None or wall < prev[1]:
                out[name] = (wall * 1e6 / events, wall, scenario.build_seconds)
    return out


def _faults_shape(smoke: bool) -> Tuple[int, float, float, float, float, float]:
    """(cams, duration_s, crash_t0, outage_s, t_kill, snapshot_period_s).

    The crash window closes well before the horizon so post-heal budget
    recovery is measurable, and the driver is killed after at least one
    snapshot past the heal so the replay covers the whole fault."""
    if smoke:
        return 300, 150.0, 50.0, 40.0, 120.0, 30.0
    return 1000, 600.0, 300.0, 120.0, 500.0, 60.0


def _faults_cfg(cams: int, dur: float, crash_t0: float, outage_s: float,
                batcher_kw: Dict) -> ScenarioConfig:
    from repro.sim import HostCrash

    return ScenarioConfig(
        num_cameras=cams, duration_s=dur, seed=0, tl="bfs",
        drops_enabled=True, avoid_drop_positives=True,
        dynamism=DynamismSpec((HostCrash(("node0",), t_start=crash_t0,
                                         outage_s=outage_s),)),
        **batcher_kw,
    )


def _retime_faults(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    """Re-time the uninterrupted journaled crash runs (the recorded
    ``us_per_event`` basis); the kill/restore cycle is derived-only."""
    from repro.query import MultiQueryScenario
    from repro.serving.journal import Journal
    from repro.sim import WorldKey, get_world

    cams, dur, crash_t0, outage_s, _t_kill, period = _faults_shape(ctx.smoke)
    out: Dict[str, Tuple[float, float, float]] = {}
    for bname, bkw in DYNAMISM_BATCHERS[:2]:
        name = f"crash_{bname}"
        if name not in cases:
            continue
        cfg = _faults_cfg(cams, dur, crash_t0, outage_s, bkw)
        get_world(WorldKey.from_config(cfg))
        for _ in range(2 if ctx.smoke else 1):
            t0 = monotonic()
            scenario = MultiQueryScenario(cfg, 2, journal=Journal(period))
            res = scenario.run()
            wall = monotonic() - t0
            events = max(res.result.source_events, 1)
            prev = out.get(name)
            if prev is None or wall < prev[1]:
                out[name] = (wall * 1e6 / events, wall, scenario.build_seconds)
    return out


#: Benchmark families the --compare gate knows how to re-time.  Families
#: present in the baseline but unknown here — or known here but absent from
#: a frozen baseline recorded before the family existed — are skipped with
#: a notice instead of failing the gate.
COMPARABLE_FAMILIES = {
    "pipeline": _retime_pipeline,
    "queries": _retime_queries,
    "faults": _retime_faults,
}


def compare_against(path: str, ctx) -> int:
    """Re-time the comparable benchmark families recorded in ``path`` (same
    mode) and return non-zero when any us_per_event regressed past the
    tolerance.  Families absent from the baseline are tolerated (a frozen
    baseline recorded before a benchmark family existed must not fail the
    gate); the gate only errors (status 2) when *nothing* was comparable."""
    with open(path) as f:
        data = json.load(f)
    mode = _mode_label(ctx)
    records = data.get("records", [])
    for r in records:
        # Baselines recorded before the run_s/xfer_s split (and before the
        # observability columns): backfill as null (unknown) rather than
        # zero (measured).
        r.setdefault("xfer_s", None)
        r.setdefault("jit_compiles", None)
        r.setdefault("metrics_overhead_s", None)
    failed = False
    compared_any = False
    print(f"{SEP}\n# Regression gate vs {path} (mode={mode}, tol={ctx.compare_tolerance:.0%})")
    for bench, retimer in COMPARABLE_FAMILIES.items():
        baselines = {
            r["case"]: float(r["us_per_event"])
            for r in records
            if r.get("bench") == bench and r.get("mode", "full") == mode
        }
        if not baselines:
            print(f"compare: no {bench!r} records for mode={mode!r} in {path} "
                  "(family absent from baseline - tolerated)")
            continue
        current = retimer(ctx, set(baselines))
        for name, base_us in sorted(baselines.items()):
            cur = current.get(name)
            if cur is None:
                # Baseline case this harness does not re-time (renamed, or a
                # derived-only record like the admission demos): skip.
                print(f"compare_{name},n/a,not retimed by this harness - skipped")
                continue
            us, run_s, build_s = cur
            ratio = us / base_us
            verdict = "OK" if ratio <= 1.0 + ctx.compare_tolerance else "REGRESSED"
            failed |= verdict != "OK"
            compared_any = True
            derived = f"baseline={base_us:.1f};ratio={ratio:.2f};{verdict}"
            record(f"{bench}_compare", name, us, derived,
                   run_s=round(run_s, 4), build_s=round(build_s, 4), mode=mode)
            print(f"compare_{name},{us:.1f},{derived}")
    if not compared_any:
        print(f"compare: nothing comparable for mode={mode!r} in {path}")
        return 2
    return 1 if failed else 0


# --------------------------------------------------------------------- #
# Figure sweeps                                                          #
# --------------------------------------------------------------------- #
def bench_batching_fig567(ctx) -> None:
    _run_grid("fig567", ctx)


def bench_tracking_fig10(ctx) -> None:
    _run_grid("fig10", ctx)


def bench_dropping_fig11(ctx) -> None:
    _run_grid("fig11", ctx)


def bench_network_fig9(ctx) -> None:
    _run_grid("fig9", ctx)


def bench_app2_fig12(ctx) -> None:
    _run_grid("fig12", ctx)


def bench_apps(ctx) -> None:
    """Table-1 apps through the app compiler: all four apps x {dynamic, nob}
    batching as one (app, deployment) sweep.  Smoke-sized by construction
    (the examples' 300-camera / 60 s workload) so app-level perf is tracked
    on every run; App 4 keeps the grid off auto-fork (JAX in workers)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from examples.apps import table1_grid

    print(f"{SEP}\n# Table-1 apps via compile_app — four apps x dynamic/nob batching")
    grid = []
    for batching in ("dynamic", "nob"):
        grid.extend(
            (f"{name}_{batching}", case) for name, case in table1_grid(batching)
        )
    res = _runner(ctx).run(grid)
    for rec in res.records:
        print(record_case("apps", rec, mode=_mode_label(ctx)))
    _sweep_record("apps", res, ctx)


# --------------------------------------------------------------------- #
# Dynamism grid (§4.3-§4.5, Figs. 7/9): DB vs SB vs NOB under transient   #
# perturbations, with per-task telemetry + budget-recovery analysis.      #
# --------------------------------------------------------------------- #

#: Batcher knobs compared under every perturbation (the paper's §5.1 set).
DYNAMISM_BATCHERS = (
    ("DB-25", dict(batching="dynamic", m_max=25)),
    ("SB-20", dict(batching="static", static_batch=20)),
    ("NOB-25", dict(batching="nob", m_max=25)),
)


def dynamism_grid(smoke: bool) -> List[Tuple[str, ScenarioConfig]]:
    """DB/SB/NOB under a transient bandwidth collapse and a transient
    compute slowdown, drops enabled, telemetry + ground-truth quality on.

    The collapse factor is far below Fig. 9's 0.03 because the network
    model charges transits independently (no shared-link queueing): the
    per-event serialization delay must itself become comparable to the
    budgets for the perturbation to bite.  Windows close before the run
    ends so budget *recovery* (§4.5.2 probes + accepts) is measurable.
    """
    if smoke:
        cams, dur, w0, w1 = 300, 150.0, 50.0, 90.0
    else:
        cams, dur, w0, w1 = 1000, 600.0, 300.0, 420.0
    perturbs = [
        ("bwcollapse", DynamismSpec((BandwidthCollapse(w0, w1, 2e-5),))),
        ("cpuslow", DynamismSpec((ComputeSlowdown(w0, w1, 6.0, hosts=("node",)),))),
    ]
    grid = []
    for pname, spec in perturbs:
        for bname, bkw in DYNAMISM_BATCHERS:
            cfg = ScenarioConfig(
                num_cameras=cams, duration_s=dur, seed=0, tl="bfs",
                drops_enabled=True, avoid_drop_positives=True,
                dynamism=spec, **bkw,
            )
            grid.append((f"{pname}_{bname}", cfg))
    return grid


def bench_dynamism(ctx) -> None:
    print(f"{SEP}\n# Dynamism grid — DB vs SB vs NOB under transient perturbations")
    res = _runner(ctx).run(dynamism_grid(ctx.smoke))
    nan = float("nan")
    for rec in res.records:
        s = rec.summary
        # Absent budget fields (a case whose budgets never initialized)
        # print as nan — float()-parsable by the smoke gate, which then
        # fails its recovery assertion with a readable value.
        derived = (
            f"beta_pre={s.get('beta_pre', nan)};beta_post={s.get('beta_post', nan)};"
            f"beta_recovery={s.get('beta_recovery', nan)};recall={s.get('track_recall')};"
            f"precision={s.get('track_precision')};dropped_frac={s['dropped_frac']};"
            f"median_lat_s={s['median_latency_s']};p99_s={s['p99_latency_s']};"
            f"probes={s.get('probes')};events={s['source_events']}"
        )
        record(
            "dynamism", rec.name, rec.us_per_event, derived,
            run_s=round(rec.run_s, 4), build_s=round(rec.build_s, 4),
            mode=_mode_label(ctx),
        )
        print(f"{rec.name},{rec.us_per_event:.1f},{derived}")
    _sweep_record("dynamism", res, ctx)


# --------------------------------------------------------------------- #
# Multi-query tenancy grid: N concurrent queries fused over ONE pipeline  #
# vs the per-query-serial baseline, plus the admission-control demo.      #
# --------------------------------------------------------------------- #
def _queries_shape(smoke: bool) -> Tuple[int, float, Tuple[int, ...]]:
    """(num_cameras, duration_s, N sweep) for the scaling part."""
    if smoke:
        return 300, 60.0, (1, 4, 16)
    return 1000, 600.0, (1, 4, 16, 64)


def _queries_cfg(cams: int, dur: float) -> ScenarioConfig:
    return ScenarioConfig(
        num_cameras=cams, duration_s=dur, seed=0, tl="bfs",
        batching="dynamic", m_max=25,
    )


def _admission_queries(cams: int, w0: float):
    """64 submitted queries: 2 well-behaved baselines at t=0 plus a
    62-query storm starting 10 s before the perturbation window, seeded at
    scattered last-seen hints (growing spotlights = genuine load)."""
    from repro.query import QuerySpec

    specs = [QuerySpec(submit_at=0.0), QuerySpec(submit_at=0.0, tl_peak_speed=5.0)]
    specs += [
        QuerySpec(
            submit_at=w0 - 10.0 + 1.0 * i,
            last_seen_camera=(i * 37) % cams,
            tl_peak_speed=4.0 + (i % 3),
        )
        for i in range(62)
    ]
    return specs


def bench_queries(ctx) -> None:
    from repro.query import AdmissionPolicy, MultiQueryScenario, run_queries_serial
    from repro.sim import ComputeSlowdown, DynamismSpec, WorldKey, get_world

    print(f"{SEP}\n# Multi-query tenancy — fused N-query runs vs per-query serial")
    cams, dur, ns = _queries_shape(ctx.smoke)
    cfg = _queries_cfg(cams, dur)
    get_world(WorldKey.from_config(cfg))  # warm the world cache for both sides
    # Best-of-2 on both sides: the smoke-scale walls are tens of ms, where
    # a single scheduler hiccup on a shared CI container flips the ratio.
    reps = 2 if ctx.smoke else 1
    for n in ns:
        fused_wall = math.inf
        for _ in range(reps):
            t0 = monotonic()
            res = MultiQueryScenario(cfg, n).run()
            fused_wall = min(fused_wall, monotonic() - t0)
        serial_wall = math.inf
        for _ in range(reps):
            serial_results, wall = run_queries_serial(cfg, n)
            serial_wall = min(serial_wall, wall)
        bit_identical = all(
            res.per_query_summary(qid) == serial_results[i].summary()
            for i, qid in enumerate(sorted(res.per_query))
        )
        s = res.summary()
        events = max(s["source_events"], 1)
        derived = (
            f"n_queries={n};wall_s={fused_wall:.3f};serial_wall_s={serial_wall:.3f};"
            f"speedup_x={serial_wall / fused_wall:.2f};bit_identical={bit_identical};"
            f"union_peak={s['union_peak_active']};union_mean={s['union_mean_active']};"
            f"events={s['source_events']};per_query_sourced={s['per_query_sourced_sum']}"
        )
        record("queries", f"fused_N{n}", fused_wall * 1e6 / events, derived,
               run_s=round(fused_wall, 4), mode=_mode_label(ctx))
        print(f"fused_N{n},{fused_wall * 1e6 / events:.1f},{derived}")

    # Admission-control demo: a 64-query storm under a ComputeSlowdown
    # window; with admission ON the CR-tier budget (held at VA, one per CR
    # downstream - paper §4.3.4) recovers while serving, with it OFF it
    # does not.  `until=duration` bounds the recovery metric to the serving
    # window: once sourcing stops, the drain always re-inflates budgets.
    a_cams, a_dur, w0, w1 = (300, 150.0, 50.0, 90.0)
    spec = DynamismSpec((ComputeSlowdown(w0, w1, 6.0, hosts=("node",)),))
    policies = (
        ("admission_off", None),
        ("admission_on", AdmissionPolicy(beta_floor=0.75, max_live=8)),
    )
    for name, policy in policies:
        a_cfg = ScenarioConfig(
            num_cameras=a_cams, duration_s=a_dur, seed=0, tl="bfs",
            batching="dynamic", m_max=25, drops_enabled=True,
            avoid_drop_positives=True, dynamism=spec,
        )
        t0 = monotonic()
        res = MultiQueryScenario(
            a_cfg, _admission_queries(a_cams, w0), admission=policy
        ).run()
        wall = monotonic() - t0
        s = res.summary()
        rec = res.result.trace.budget_recovery("VA", until=a_dur)
        derived = (
            f"beta_pre={rec['pre']:.3f};beta_post={rec['post']:.3f};"
            f"beta_recovery={rec['recovery']:.3f};live_end={s['queries_live_end']};"
            f"found={s['queries_found']};union_peak={s['union_peak_active']};"
            f"dropped_frac={s['dropped_frac']};"
            f"admitted={s.get('adm_admitted', 64)};queued={s.get('adm_queued', 0)}"
        )
        record("queries", name, wall * 1e6 / max(s["source_events"], 1), derived,
               run_s=round(wall, 4), mode=_mode_label(ctx))
        print(f"{name},{wall * 1e6 / max(s['source_events'], 1):.1f},{derived}")


# --------------------------------------------------------------------- #
# Mega-step engine — the fused device scan vs the interpreted hot loop    #
# --------------------------------------------------------------------- #
def _megastep_shape(smoke: bool) -> Tuple[int, float, Tuple[int, ...]]:
    """(num_cameras, duration_s, N sweep) for the engine comparison."""
    if smoke:
        return 300, 60.0, (1, 4, 16)
    return 10_000, 600.0, (1, 16, 64)


def _megastep_specs(n: int, cams: int):
    """N weighted-ball queries tracking the entity (warm-started from the
    walk, mixed peak speeds).  This is the paper's steady-tracking regime:
    detections keep resetting each spotlight, so the union stays bounded
    and the run sits inside the 10-lane service capacity (~83 events/tick
    at the default 120 ms CR cost) — the operating point where the fused
    scan stays device-resident instead of overflowing to the host mirror.
    Scattering seeds across 10k cameras instead makes every ball grow
    unbounded (no detections), overloads the lanes within seconds, and
    every engine degenerates to measuring the backlog."""
    from repro.query import QuerySpec

    return [QuerySpec(tl="wbfs", tl_peak_speed=3.0 + (i % 3))
            for i in range(n)]


def _time_megastep_fused(cfg, specs_of, reps: int):
    """Best-of-``reps`` fused run (the first rep eats the scan compile);
    returns (wall, xfer, engine, result)."""
    import copy

    from repro.query import MultiQueryScenario

    best = (math.inf, 0.0, "?", None)
    m_cfg = copy.deepcopy(cfg)
    m_cfg.engine = "megastep"
    for _ in range(reps):
        t0 = monotonic()
        scn = MultiQueryScenario(m_cfg, specs_of())
        res = scn.run()
        wall = monotonic() - t0
        if wall < best[0]:
            best = (wall, scn.engine_xfer_s, scn.engine_used, res)
    return best


def bench_megastep(ctx) -> None:
    from repro.query import MultiQueryScenario
    from repro.sim import WorldKey, get_world

    print(f"{SEP}\n# Mega-step — fused device scan vs per-op spotlight vs interpreted")
    cams, dur, ns = _megastep_shape(ctx.smoke)
    cfg = _queries_cfg(cams, dur)
    get_world(WorldKey.from_config(cfg))
    reps = 2 if ctx.smoke else 1
    for n in ns:
        specs_of = lambda: _megastep_specs(n, cams)
        interp_wall = math.inf
        for _ in range(reps):
            t0 = monotonic()
            ref = MultiQueryScenario(cfg, specs_of()).run()
            interp_wall = min(interp_wall, monotonic() - t0)
        # The per-op column (kernel spotlight mode: one device ball
        # dispatch per TL tick) shows what per-op offload costs vs the
        # fused scan.  It only runs at the smallest N of the smoke shape:
        # per-tick dense relaxation over a 10k-camera graph is infeasible
        # by orders of magnitude (that cliff is the point — see PERF.md),
        # and repeating it per N would dominate the CI step for a number
        # that barely varies with N.
        perop_wall = math.inf
        if ctx.smoke and n == ns[0]:
            t0 = monotonic()
            MultiQueryScenario(cfg, specs_of(), spotlight_mode="kernel").run()
            perop_wall = monotonic() - t0
        # Two fused reps minimum: the first pays the one-off scan compile,
        # the steady-state rate is what the engine claims.
        wall, xfer, engine, res = _time_megastep_fused(
            cfg, specs_of, max(reps, 2)
        )
        bit_identical = res.result.summary() == ref.result.summary() and all(
            res.per_query_summary(q) == ref.per_query_summary(q)
            for q in res.per_query
        )
        events = max(res.result.source_events, 1)
        us = wall * 1e6 / events
        perop_us = (
            f"{perop_wall * 1e6 / events:.1f}"
            if math.isfinite(perop_wall) else "n/a"
        )
        derived = (
            f"n_queries={n};engine={engine};bit_identical={bit_identical};"
            f"interp_us={interp_wall * 1e6 / events:.1f};"
            f"perop_us={perop_us};"
            f"speedup_x={interp_wall / wall:.2f};events={events};"
            f"union_peak={res.summary()['union_peak_active']}"
        )
        record("megastep", f"engine_N{n}", us, derived,
               run_s=round(wall - xfer, 4), xfer_s=xfer,
               mode=_mode_label(ctx))
        print(f"megastep_engine_N{n},{us:.1f},{derived}")


def _retime_megastep(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    """Re-time the fused side only (the recorded us_per_event basis)."""
    from repro.sim import WorldKey, get_world

    cams, dur, ns = _megastep_shape(ctx.smoke)
    cfg = _queries_cfg(cams, dur)
    get_world(WorldKey.from_config(cfg))
    out: Dict[str, Tuple[float, float, float]] = {}
    for n in ns:
        name = f"engine_N{n}"
        if name not in cases:
            continue
        wall, _xfer, _engine, res = _time_megastep_fused(
            cfg, lambda: _megastep_specs(n, cams), 2
        )
        events = max(res.result.source_events, 1)
        out[name] = (wall * 1e6 / events, wall, 0.0)
    return out


# (registered post-definition: COMPARABLE_FAMILIES is declared with the
# early retimers, before this family exists in the file)
COMPARABLE_FAMILIES["megastep"] = _retime_megastep


# --------------------------------------------------------------------- #
# Sharded mega-step — the fused scan over a camera mesh (shard scaling)   #
# --------------------------------------------------------------------- #
def _sharded_shape(smoke: bool) -> Tuple[int, float, Tuple[int, ...]]:
    """(num_cameras, duration_s, N sweep) for the shard-scaling sweep.
    Smaller full shape than the unsharded family: the sweep multiplies by
    the shard counts, and emulated host devices share one CPU."""
    if smoke:
        return 300, 60.0, (1, 4)
    return 1000, 300.0, (1, 16, 64)


def _shard_counts() -> Tuple[int, ...]:
    """Mesh widths to sweep: the divisors of the visible device count in
    {1, 2, 4, 8}.  Under CI this runs with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with a single
    visible device only the single-shard baseline records."""
    try:
        import jax

        ndev = len(jax.devices())
    except ImportError:
        return (1,)
    return tuple(d for d in (1, 2, 4, 8) if d <= ndev)


def _time_sharded(cfg, specs_of, reps: int, shards: int):
    """Best-of-``reps`` sharded run (first rep eats the per-mesh compile);
    returns (wall, xfer, scn, result)."""
    import copy

    from repro.query import MultiQueryScenario

    mesh = None
    if shards > 1:
        import jax

        from repro.distributed import camera_mesh

        mesh = camera_mesh(jax.devices()[:shards])
    best = (math.inf, 0.0, None, None)
    m_cfg = copy.deepcopy(cfg)
    m_cfg.engine = "megastep"
    for _ in range(reps):
        t0 = monotonic()
        scn = MultiQueryScenario(m_cfg, specs_of(), mesh=mesh)
        res = scn.run()
        wall = monotonic() - t0
        if wall < best[0]:
            best = (wall, scn.engine_xfer_s, scn, res)
    return best


def bench_sharded(ctx) -> None:
    from repro.sim import WorldKey, get_world

    print(f"{SEP}\n# Sharded mega-step — per-event wall vs camera-mesh width")
    cams, dur, ns = _sharded_shape(ctx.smoke)
    shard_counts = _shard_counts()
    cfg = _queries_cfg(cams, dur)
    get_world(WorldKey.from_config(cfg))
    for n in ns:
        specs_of = lambda: _megastep_specs(n, cams)
        base_res = None
        for d in shard_counts:
            wall, xfer, scn, res = _time_sharded(cfg, specs_of, 2, d)
            if d == shard_counts[0]:
                base_res = res
            # Sharding is only allowed to change the wall clock: per-query
            # and global books must match the single-shard run exactly.
            bit_identical = (
                res.result.summary() == base_res.result.summary()
                and all(
                    res.per_query_summary(q) == base_res.per_query_summary(q)
                    for q in res.per_query
                )
            )
            events = max(res.result.source_events, 1)
            us = wall * 1e6 / events
            derived = (
                f"n_queries={n};shards={scn.shards_used};"
                f"engine={scn.engine_used};bit_identical={bit_identical};"
                f"collective_bytes_per_tick={scn.collective_bytes_per_tick:.0f};"
                f"shard_fallback={scn.shard_fallback_reason or 'none'};"
                f"events={events}"
            )
            record("sharded", f"N{n}_D{d}", us, derived,
                   run_s=round(wall - xfer, 4), xfer_s=xfer,
                   mode=_mode_label(ctx))
            print(f"sharded_N{n}_D{d},{us:.1f},{derived}")


def _retime_sharded(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    cams, dur, ns = _sharded_shape(ctx.smoke)
    cfg = _queries_cfg(cams, dur)
    from repro.sim import WorldKey, get_world

    get_world(WorldKey.from_config(cfg))
    out: Dict[str, Tuple[float, float, float]] = {}
    for n in ns:
        for d in _shard_counts():
            name = f"N{n}_D{d}"
            if name not in cases:
                continue
            wall, _xfer, _scn, res = _time_sharded(
                cfg, lambda: _megastep_specs(n, cams), 2, d
            )
            events = max(res.result.source_events, 1)
            out[name] = (wall * 1e6 / events, wall, 0.0)
    return out


COMPARABLE_FAMILIES["sharded"] = _retime_sharded


# --------------------------------------------------------------------- #
# Fault tolerance — mid-run host crash under DB vs SB: journaled          #
# kill/restore/replay cycle (recovery time, bit-identity) + post-heal     #
# budget recovery.                                                        #
# --------------------------------------------------------------------- #
def bench_faults(ctx) -> None:
    from repro.query import MultiQueryScenario
    from repro.serving.journal import Journal
    from repro.sim import WorldKey, get_world

    print(f"{SEP}\n# Fault tolerance — host crash, journaled restore, DB vs SB")
    cams, dur, crash_t0, outage_s, t_kill, period = _faults_shape(ctx.smoke)
    heal = crash_t0 + outage_s
    for bname, bkw in DYNAMISM_BATCHERS[:2]:  # DB vs SB (the ISSUE pairing)
        cfg = _faults_cfg(cams, dur, crash_t0, outage_s, bkw)
        get_world(WorldKey.from_config(cfg))  # warm: baselines are warm too

        # Reference: the uninterrupted journaled run (us_per_event basis).
        t0 = monotonic()
        ref = MultiQueryScenario(cfg, 2, journal=Journal(period))
        ref_res = ref.run()
        wall = monotonic() - t0

        # Kill the driver at t_kill; only its journal (WAL) survives.
        crashed = MultiQueryScenario(cfg, 2, journal=Journal(period))
        crashed.run_until(t_kill)
        wal = crashed.journal
        restore_to = wal.last_snapshot()["time"]

        # Recovery = build a fresh scenario + replay to the last snapshot
        # (bit-verified against the WAL's frontier), then serve to the end.
        t0 = monotonic()
        recovered = MultiQueryScenario(cfg, 2, journal=Journal(period))
        recovered.restore(wal)
        recovery_s = monotonic() - t0
        rec_res = recovered.run()

        bit_identical = (
            all(rec_res.per_query_summary(q) == ref_res.per_query_summary(q)
                for q in ref_res.per_query)
            and recovered.journal.digest() == ref.journal.digest()
        )
        s = ref_res.summary()
        events = max(s["source_events"], 1)
        brec = ref_res.result.trace.budget_recovery("VA", until=dur)
        fault_drops = ref.sim.faults.fault_drops
        derived = (
            f"crash=node0@[{crash_t0:g},{heal:g});t_kill={t_kill:g};"
            f"snap_period_s={period:g};restore_to={restore_to:g};"
            f"recovery_s={recovery_s:.3f};bit_identical={bit_identical};"
            f"dp_fault={fault_drops};retries={ref.sim.faults.retries};"
            f"beta_pre={brec['pre']:.3f};beta_post={brec['post']:.3f};"
            f"beta_recovery={brec['recovery']:.3f};"
            f"dropped_frac={s['dropped_frac']};events={s['source_events']}"
        )
        record("faults", f"crash_{bname}", wall * 1e6 / events, derived,
               run_s=round(wall, 4), mode=_mode_label(ctx))
        print(f"crash_{bname},{wall * 1e6 / events:.1f},{derived}")


def bench_scale_fig13(ctx) -> None:
    _run_grid("fig13", ctx)
    # Multi-entity probabilistic spotlight: bucket-batched CSR relaxation
    # kernel (via repro.kernels.dispatch) vs the incremental python path.
    from repro.core.roadnet import make_road_network
    from repro.core.tracking import TLProbabilistic

    net = make_road_network(seed=0)
    cams = {c: c for c in range(net.num_vertices)}
    tl = TLProbabilistic(net, cams, entity_speed=4.0, coverage=0.9)
    for i in range(8):
        tl.track(f"entity{i}", camera_id=(i * 97) % net.num_vertices, timestamp=float(i))
    for label, use_kernel in (("python", False), ("kernel", True)):
        tl._entity_searches.clear()
        t0 = monotonic()
        active = tl.spotlight_multi(60.0, use_kernel=use_kernel)
        us = (monotonic() - t0) * 1e6
        record("fig13", f"multi_entity_{label}", us / 8.0,
               f"entities=8;active={len(active)}", mode=_mode_label(ctx))
        print(f"multi_entity_{label},{us/8.0:.1f},entities=8;active={len(active)}")


# --------------------------------------------------------------------- #
# Kernel micro-benchmarks (CPU: oracle path; TPU would hit Pallas)       #
# --------------------------------------------------------------------- #
def bench_kernels(ctx=None) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.reid_match.ops import reid_match
    from repro.kernels.spotlight_ball.ops import spotlight_ball
    from repro.kernels.ssd_scan.ops import ssd_scan

    print(f"{SEP}\n# Kernel micro-benchmarks (CPU reference path)")
    key = jax.random.PRNGKey(0)

    def timeit(name, fn, *args, reps=5, derived=""):
        fn(*args)  # compile
        t0 = monotonic()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        us = (monotonic() - t0) / reps * 1e6
        record("kernels", name, us, derived)
        print(f"{name},{us:.1f},{derived}")

    B, S, H, Hkv, D = 1, 1024, 8, 2, 64
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    timeit("flash_attention_1k", flash_attention, q, k, v,
           derived=f"flops={2*2*B*S*S*H*D:.2e}")

    qd = jax.random.normal(key, (8, H, D))
    # head-major cache layout (B, Hkv, T, D)
    kc = jax.random.normal(key, (8, Hkv, 4096, D))
    vc = jax.random.normal(key, (8, Hkv, 4096, D))
    ln = jnp.full((8,), 4096, jnp.int32)
    timeit("decode_attention_4k", decode_attention, qd, kc, vc, ln,
           derived=f"kv_bytes={8*4096*Hkv*D*2*4:.2e}")

    x = jax.random.normal(key, (1, 1024, 8, 64)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(key, (1, 1024, 8)))
    A = -jnp.exp(jax.random.normal(key, (8,)) * 0.3)
    Bm = jax.random.normal(key, (1, 1024, 1, 64)) * 0.3
    Cm = jax.random.normal(key, (1, 1024, 1, 64)) * 0.3
    timeit("ssd_scan_1k", lambda *a: ssd_scan(*a)[0], x, dt, A, Bm, Cm,
           derived="chunked state-space scan")

    g = jax.random.normal(key, (4096, 128))
    qq = jax.random.normal(key, (4, 128))
    timeit("reid_match_4k", lambda *a: reid_match(*a)[0], g, qq,
           derived="gallery=4096x128")

    from repro.core.roadnet import make_road_network

    net = make_road_network(num_vertices=512, target_edges=1442, seed=0)
    indptr, indices, weights = net.csr()
    rng = np.random.default_rng(0)
    sources = rng.integers(0, 512, size=16).astype(np.int32)
    radii = rng.uniform(100, 1500, size=16).astype(np.float32)
    timeit(
        "spotlight_ball_512v_16q",
        lambda: spotlight_ball(indptr, indices, weights.astype(np.float32), sources, radii),
        derived="V=512;Q=16;dense min-plus relaxation",
    )


# --------------------------------------------------------------------- #
# Roofline table from the dry-run records (§Roofline source of truth)    #
# --------------------------------------------------------------------- #
def bench_roofline(ctx=None, out_dir: str = "experiments/dryrun") -> None:
    print(f"{SEP}\n# Roofline table (from {out_dir}/*.json; see EXPERIMENTS.md)")
    recs = []
    for path in sorted(glob.glob(f"{out_dir}/*.json")):
        with open(path) as f:
            recs.append(json.load(f))
    if not recs:
        print("roofline,0,missing (run: python -m repro.launch.dryrun --mesh both)")
        return
    print(
        "arch,shape,mesh,compute_ms,memory_ms,collective_ms,dominant,"
        "useful_ratio,peak_dev_GiB,compile_s"
    )
    for r in recs:
        t = r["roofline"]
        print(
            f"{r['arch']},{r['shape']},{r['mesh']},"
            f"{t['compute_s']*1e3:.3f},{t['memory_s']*1e3:.3f},"
            f"{t['collective_s']*1e3:.3f},{t['dominant']},"
            f"{t['useful_ratio']:.3f},{r['peak_device_bytes']/2**30:.2f},"
            f"{r['compile_s']}"
        )


# --------------------------------------------------------------------- #
# Anveshak-scheduled LM serving stage                                    #
# --------------------------------------------------------------------- #
def bench_serving(ctx=None) -> None:
    import jax
    import jax.numpy as jnp

    from repro.serving import ServedStage, StageRequest, calibrate_xi, embed_frames, init_reid_tower

    print(f"{SEP}\n# Anveshak-scheduled serving stage (budgeted dynamic batching)")
    tower = init_reid_tower(jax.random.PRNGKey(0), d_in=128, d_embed=64)
    step = lambda x: embed_frames(tower, jnp.asarray(x))
    xi = calibrate_xi(step, (128,), buckets=(1, 4, 16, 64))
    for rate_hz in (50, 200, 1000):
        stage = ServedStage("CR", step, xi, gamma=0.5, m_max=64, buckets=(1, 4, 16, 64))
        n, done, dropped = 200, 0, 0
        t0 = monotonic()
        for i in range(n):
            target = t0 + i / rate_hz
            while monotonic() < target:
                pass
            res = stage.submit(StageRequest(np.zeros(128, np.float32), source_time=target))
            for r in res or []:
                done += 0 if r.dropped else 1
                dropped += 1 if r.dropped else 0
        for r in stage.flush() or []:
            done += 0 if r.dropped else 1
            dropped += 1 if r.dropped else 0
        wall = monotonic() - t0
        sizes = stage.stats["executed"] / max(stage.stats["batches"], 1)
        record("serving", f"serving_rate{rate_hz}", wall / n * 1e6,
               f"done={done};dropped={dropped};mean_batch={sizes:.1f}")
        print(
            f"serving_rate{rate_hz},{wall/n*1e6:.1f},"
            f"done={done};dropped={dropped};mean_batch={sizes:.1f};"
            f"throughput_hz={done/wall:.0f}"
        )


# --------------------------------------------------------------------- #
# Observability plane: exporter overhead, on vs off                       #
# --------------------------------------------------------------------- #
def _obs_shape(smoke: bool) -> Tuple[int, float]:
    return (300, 60.0) if smoke else (1000, 300.0)


def _obs_case(ctx, case: str) -> Tuple[float, float, float, float, int]:
    """One obs-family measurement on a warm world.

    Cases: ``export_off`` runs the bare pipeline; ``export_on`` adds metric
    collection + Prometheus exposition after the run (the exporter price —
    the hot loop is untouched); ``traced_on`` additionally installs the
    sampled span tracer, which disables the bulk static-delivery fast path
    so every hop is observed (the full-fidelity price).

    Returns ``(us_per_event, run_s, build_s, overhead_s, jit_compiles)``
    where ``overhead_s`` is the wall spent *outside* the run in collection
    and export (0.0 when off) and ``jit_compiles`` is the kernel-plane
    compile count consumed during the case."""
    from repro.kernels import dispatch
    from repro.obs import EventTracer, MetricsRegistry, prometheus_exposition
    from repro.sim import TrackingScenario, WorldKey, get_world

    cams, dur = _obs_shape(ctx.smoke)
    tracer = EventTracer(stride=64) if case == "traced_on" else None
    cfg = ScenarioConfig(num_cameras=cams, duration_s=dur, seed=0, tracer=tracer)
    get_world(WorldKey.from_config(cfg))
    compiles0 = sum(dispatch.profile()["compiles"].values())
    t0 = monotonic()
    scenario = TrackingScenario(cfg)
    res = scenario.run()
    run_s = monotonic() - t0
    overhead_s = 0.0
    if case != "export_off":
        m0 = monotonic()
        reg = MetricsRegistry()
        scenario.publish_metrics(reg, res)
        prometheus_exposition(reg)
        overhead_s = monotonic() - m0
    compiles = sum(dispatch.profile()["compiles"].values()) - compiles0
    events = max(res.source_events, 1)
    us = (run_s + overhead_s) * 1e6 / events
    return us, run_s, scenario.build_seconds, overhead_s, compiles


OBS_CASES = ("export_off", "export_on", "traced_on")


def bench_obs(ctx) -> None:
    """Exporter overhead: the pipeline workload with the obs plane off,
    with metrics collection + exposition (exporters), and with the sampled
    span tracer on top.  The on-case ``us_per_event`` includes collection/
    export wall so the recorded ratio *is* the user-visible overhead."""
    reps = 2
    print(f"{SEP}\n# Observability overhead — obs plane off vs on (best of {reps})")
    best: Dict[str, Tuple[float, float, float, float, int]] = {}
    for case in OBS_CASES:
        for _ in range(reps):
            cur = _obs_case(ctx, case)
            prev = best.get(case)
            if prev is None or cur[0] < prev[0]:
                best[case] = cur
    off_us = best["export_off"][0]
    cams, dur = _obs_shape(ctx.smoke)
    for case in OBS_CASES:
        us, run_s, build_s, overhead_s, compiles = best[case]
        ratio = us / max(off_us, 1e-9)
        derived = (
            f"cams={cams};dur_s={dur:g};overhead_s={overhead_s:.4f};"
            f"vs_off_x={ratio:.3f};build_s={build_s:.3f}"
        )
        record(
            "obs", case, us, derived,
            run_s=round(run_s, 4), build_s=round(build_s, 4),
            mode=_mode_label(ctx),
            jit_compiles=compiles,
            metrics_overhead_s=overhead_s,
        )
        print(f"obs_{case},{us:.1f},{derived}")


def _retime_obs(ctx, cases) -> Dict[str, Tuple[float, float, float]]:
    out: Dict[str, Tuple[float, float, float]] = {}
    for case in OBS_CASES:
        if case not in cases:
            continue
        for _ in range(2):
            us, run_s, build_s, _ovh, _jc = _obs_case(ctx, case)
            prev = out.get(case)
            if prev is None or us < prev[0]:
                out[case] = (us, run_s, build_s)
    return out


COMPARABLE_FAMILIES["obs"] = _retime_obs


BENCHES = {
    "pipeline": bench_pipeline,
    "apps": bench_apps,
    "dynamism": bench_dynamism,
    "queries": bench_queries,
    "megastep": bench_megastep,
    "sharded": bench_sharded,
    "faults": bench_faults,
    "fig567": bench_batching_fig567,
    "fig10": bench_tracking_fig10,
    "fig11": bench_dropping_fig11,
    "fig9": bench_network_fig9,
    "fig12": bench_app2_fig12,
    "fig13": bench_scale_fig13,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "serving": bench_serving,
    "obs": bench_obs,
}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write machine-readable {bench, case, us_per_event, derived, "
        "run_s, build_s, mode} records",
    )
    ap.add_argument(
        "--mode",
        default="auto",
        choices=("auto", "fork", "serial", "cold"),
        help="sweep execution: auto/fork/serial share worlds; cold rebuilds "
        "every config's world (sequential baseline)",
    )
    ap.add_argument("--workers", type=int, default=None, help="sweep pool size")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="short scenario durations (<=60s) so CI machines finish in seconds",
    )
    ap.add_argument(
        "--compare",
        default=None,
        metavar="PATH",
        help="regression gate: re-time the pipeline cases recorded in PATH "
        "and exit non-zero on regression",
    )
    ap.add_argument("--compare-tolerance", type=float, default=0.35)
    args = ap.parse_args(argv)
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    # Benchmarks default to the on-disk world cache so repeated invocations
    # skip the one-off builds; opt out with REPRO_WORLD_CACHE=0.
    os.environ.setdefault("REPRO_WORLD_CACHE", "1")

    status = 0
    compare_only = args.compare is not None and args.only is None
    if args.compare is not None:
        status = compare_against(args.compare, args)
    if not compare_only:
        t0 = monotonic()
        for name, fn in BENCHES.items():
            if args.only and name != args.only:
                continue
            fn(args)
        print(f"{SEP}\nTotal benchmark wall time: {monotonic()-t0:.1f}s")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"harness": "benchmarks.run", "records": RECORDS}, f, indent=2)
            f.write("\n")
        print(f"wrote {len(RECORDS)} records to {args.json}")
    return status


if __name__ == "__main__":
    raise SystemExit(main())
