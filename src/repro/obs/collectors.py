"""Collectors: route every existing ad-hoc signal into the registry.

One collector per signal surface, each registering its metrics (name +
mandatory help, OBS001-checked) and filling them from the component's
already-maintained counters — collectors never add work to any hot path;
they run once, after (or on a cadence outside) the run.

Domain assignment is the determinism contract (see ``obs.metrics``):

* event/sim-state-derived values (pipeline counters, per-query ledgers,
  journal records, latency histograms, dynamism-trace samples, tracer
  spans) register as ``SIM`` and participate in exposition digests;
* engine/shard attribution, jit caches, kernel-plane profiling and
  wall-clock serving-stage counters register as ``WALL`` — they vary
  with backend, mesh width or host timing and are excluded from digests.
"""

from __future__ import annotations

from typing import Iterable, Optional

from repro.obs.metrics import SIM, WALL, MetricsRegistry

__all__ = [
    "collect_scenario",
    "collect_query_result",
    "collect_journal",
    "collect_stage",
    "collect_dispatch",
    "collect_engine",
]

#: PipelineStats attributes aggregated per module (FC/VA/CR/UV).
_TASK_KINDS = ("arrived", "executed", "batches", "probes",
               "accepts_rx", "rejects_rx")
_DROP_KINDS = (("dp1", "dropped_dp1"), ("dp2", "dropped_dp2"),
               ("dp3", "dropped_dp3"), ("dp_fault", "dropped_fault"))


def collect_scenario(registry: MetricsRegistry, scn, res) -> MetricsRegistry:
    """Single-pipeline run: global counters, per-module task stats, the
    end-to-end latency histogram, fault-plane counters and the final
    dynamism-trace sample.  All SIM-domain."""
    registry.counter(
        "repro_source_events_total",
        "Frames sourced by the active camera set over the run.",
    ).inc(res.source_events)
    sink = registry.counter(
        "repro_sink_events_total",
        "Events that completed the full path to the UV sink, by deadline "
        "outcome (on_time: u <= gamma).",
        labels=("outcome",),
    )
    sink.inc(res.on_time, outcome="on_time")
    sink.inc(res.delayed, outcome="delayed")
    lat = registry.histogram(
        "repro_sink_latency_seconds",
        "End-to-end event latency at the sink (u = sink arrival - source "
        "arrival), seconds.",
    )
    for _, u in res.latencies:
        lat.observe(u)
    pos = registry.counter(
        "repro_positives_total",
        "Ground-truth positive frames by outcome.",
        labels=("outcome",),
    )
    pos.inc(res.positives_generated, outcome="generated")
    pos.inc(res.positives_completed, outcome="completed")
    pos.inc(res.positives_dropped, outcome="dropped")
    registry.counter(
        "repro_reid_matched_total",
        "Sink detections matched by the re-id tower.",
    ).inc(res.reid_matched)
    registry.counter(
        "repro_query_pushes_total",
        "QF feedback-edge query updates pushed to VA/CR state.",
    ).inc(res.query_pushes)
    dropped = registry.counter(
        "repro_events_dropped_total",
        "Events dropped before the sink, attributed to the dropping task.",
        labels=("task",),
    )
    for task, n in sorted(res.drops_by_task.items()):
        dropped.inc(n, task=task)
    active = registry.gauge(
        "repro_active_cameras",
        "Active camera set size (spotlight scoping), final and peak.",
        labels=("stat",),
    )
    timeline = res.active_timeline
    active.set(timeline[-1][1] if timeline else 0, stat="final")
    active.set(res.peak_active, stat="peak")

    # Per-module pipeline counters (aggregated: a per-task family would be
    # one series per lazily-built FC).
    compiled = getattr(scn, "compiled", None)
    if compiled is not None:
        mod_events = registry.counter(
            "repro_module_events_total",
            "Pipeline task counters aggregated per dataflow module "
            "(FC/VA/CR/UV).",
            labels=("module", "kind"),
        )
        mod_drops = registry.counter(
            "repro_module_dropped_total",
            "Pipeline drops per module and drop point (dp1-dp3, dp_fault).",
            labels=("module", "cause"),
        )
        tasks = list(compiled.all_tasks()) + [compiled.sink]
        agg: dict = {}
        for t in tasks:
            row = agg.setdefault(t.module or t.name, {})
            for kind in _TASK_KINDS:
                row[kind] = row.get(kind, 0) + getattr(t.stats, kind)
            for cause, attr in _DROP_KINDS:
                row[cause] = row.get(cause, 0) + getattr(t.stats, attr)
        for module in sorted(agg):
            row = agg[module]
            for kind in _TASK_KINDS:
                if row[kind]:
                    mod_events.inc(row[kind], module=module, kind=kind)
            for cause, _ in _DROP_KINDS:
                if row[cause]:
                    mod_drops.inc(row[cause], module=module, cause=cause)

    # Fault plane (PR 6): retry/blocked/fault-drop books.
    faults = getattr(getattr(scn, "sim", None), "faults", None)
    if faults is not None:
        registry.counter(
            "repro_fault_sends_blocked_total",
            "Inter-task sends blocked by a crash window or partition.",
        ).inc(faults.sends_blocked)
        registry.counter(
            "repro_fault_retries_total",
            "Fault-plane transmit retries (capped exponential backoff).",
        ).inc(faults.retries)
        registry.counter(
            "repro_fault_drops_total",
            "Events lost to faults (DP_FAULT): crashed host or retries "
            "exhausted.",
        ).inc(faults.fault_drops)

    # Dynamism trace: the final sampled row per task/aggregate column.
    trace = getattr(res, "trace", None)
    if trace is not None and getattr(trace, "times", None):
        dyn = registry.gauge(
            "repro_dyn_sample",
            "Final dynamism-trace sample per task column and trace field "
            "(beta, queue, drop/signal counters).",
            labels=("task", "field"),
        )
        for task in sorted(trace.series):
            for fld, col in sorted(trace.series[task].items()):
                if col:
                    dyn.set(col[-1], task=task, field=fld)

    tracer = getattr(scn, "tracer", None)
    if tracer is not None:
        tracer.publish_metrics(registry)
    return registry


def collect_journal(registry: MetricsRegistry, journal) -> MetricsRegistry:
    """Journal record stream + snapshot books (SIM: the record stream is
    part of the exact-recovery contract, identical under restore-replay)."""
    if journal is None:
        return registry
    recs = registry.counter(
        "repro_journal_records_total",
        "Journal WAL records by kind (source/sink/drop).",
        labels=("kind",),
    )
    for kind, n in sorted(journal.counts().items()):
        if n:
            recs.inc(n, kind=kind)
    registry.counter(
        "repro_journal_snapshots_total",
        "Frontier snapshots appended by the journal tick.",
    ).inc(len(journal.snapshots))
    return registry


def collect_engine(registry: MetricsRegistry, scn) -> MetricsRegistry:
    """Engine/shard attribution for a MultiQueryScenario run.  WALL-domain
    by definition: the chosen backend, shard count and transfer walls vary
    with the host/mesh, never with the simulated system's state."""
    info = registry.gauge(
        "repro_engine_info",
        "Engine actually used for the run (value 1; fallback reason as a "
        "label, empty when none).",
        labels=("engine", "fallback_reason"),
        domain=WALL,
    )
    info.set(
        1,
        engine=getattr(scn, "engine_used", "interpreted"),
        fallback_reason=getattr(scn, "engine_fallback_reason", ""),
    )
    registry.gauge(
        "repro_engine_xfer_seconds",
        "Device->host transfer wall of the mega-step run (0 off-device).",
        domain=WALL,
    ).set(getattr(scn, "engine_xfer_s", 0.0))
    registry.gauge(
        "repro_engine_shards_used",
        "Camera-mesh shards the fused scan actually ran on.",
        domain=WALL,
    ).set(getattr(scn, "shards_used", 1))
    registry.gauge(
        "repro_engine_collective_bytes_per_tick",
        "Estimated all-reduce payload per simulated tick on the sharded "
        "engine (0 unsharded).",
        domain=WALL,
    ).set(getattr(scn, "collective_bytes_per_tick", 0.0))
    registry.gauge(
        "repro_engine_shard_fallback_info",
        "Why the sharded scan did not run (value 1; empty reason = it ran).",
        labels=("reason",),
        domain=WALL,
    ).set(1, reason=getattr(scn, "shard_fallback_reason", "no-mesh"))
    chunk_s = getattr(scn, "megastep_chunk_s", None)
    if chunk_s is not None:
        registry.gauge(
            "repro_megastep_chunk_seconds",
            "Total host wall of the mega-step scan chunks (device dispatch "
            "+ compute + summary pull).",
            domain=WALL,
        ).set(chunk_s)
        registry.gauge(
            "repro_megastep_chunks",
            "Number of K-tick scan chunks the mega-step run dispatched.",
            domain=WALL,
        ).set(getattr(scn, "megastep_chunks", 0))
    # The kernel plane is part of the engine story: dispatch counters,
    # per-bucket compile counts and jit-cache occupancy ride along.
    collect_dispatch(registry)
    return registry


def collect_query_result(registry: MetricsRegistry, scn, res) -> MetricsRegistry:
    """Multi-query run: the global scenario collectors plus per-query
    ledgers, admission books, the journal, and engine attribution."""
    collect_scenario(registry, scn, res.result)
    qev = registry.counter(
        "repro_query_events_total",
        "Per-query event ledger (sourced/completed/dropped and the orphan "
        "classes reconciling late events after cancel/expiry).",
        labels=("query", "kind"),
    )
    qdrop = registry.counter(
        "repro_query_dropped_total",
        "Per-query drops by drop point (dp1-dp3, dp_fault).",
        labels=("query", "cause"),
    )
    qpos = registry.counter(
        "repro_query_positives_total",
        "Per-query ground-truth positives by outcome.",
        labels=("query", "outcome"),
    )
    qbeta = registry.gauge(
        "repro_query_beta_seconds",
        "Per-query completion budget (beta) at end of run.",
        labels=("query",),
    )
    qstate = registry.gauge(
        "repro_query_state_info",
        "Per-query lifecycle state at end of run (value 1).",
        labels=("query", "state"),
    )
    qflight = registry.gauge(
        "repro_query_in_flight",
        "Per-query events still in flight at the horizon.",
        labels=("query",),
    )
    for qid, st in sorted(res.registry.states.items()):
        q = str(qid)
        for kind in ("sourced", "completed", "dropped", "on_time", "delayed",
                     "orphan_completed", "orphan_dropped"):
            v = getattr(st, kind)
            if v:
                qev.inc(v, query=q, kind=kind)
        for i, cause in ((1, "dp1"), (2, "dp2"), (3, "dp3"), (4, "dp_fault")):
            if st.dp[i]:
                qdrop.inc(st.dp[i], query=q, cause=cause)
        if st.positives_generated:
            qpos.inc(st.positives_generated, query=q, outcome="generated")
        if st.positives_completed:
            qpos.inc(st.positives_completed, query=q, outcome="completed")
        qbeta.set(st.beta(), query=q)
        qstate.set(1, query=q, state=st.state)
        qflight.set(st.in_flight, query=q)
    adm = res.admission
    if adm is not None:
        dec = registry.counter(
            "repro_admission_decisions_total",
            "Admission-controller decisions by verdict.",
            labels=("decision",),
        )
        for k, v in sorted(adm.decisions.items()):
            if v:
                dec.inc(v, decision=k)
        registry.gauge(
            "repro_admission_queue_len",
            "Admission queue length at end of run.",
        ).set(len(adm.queue))
    collect_journal(registry, getattr(scn, "journal", None))
    collect_engine(registry, scn)
    return registry


def collect_stage(registry: MetricsRegistry, stage,
                  query_ids: Optional[Iterable[int]] = None) -> MetricsRegistry:
    """ServedStage counters + per-query telemetry rows.  WALL-domain: the
    serving plane runs on the host clock (``core.clock.monotonic`` /
    ``time.monotonic``), so its counters are not replay-deterministic."""
    sev = registry.counter(
        "repro_stage_events_total",
        "Serving-stage counters (TRACE_FIELDS-shaped row) per stage.",
        labels=("stage", "kind"),
        domain=WALL,
    )
    sgauge = registry.gauge(
        "repro_stage_row",
        "Serving-stage budget/queue sample per stage (beta seconds, queue "
        "depth).",
        labels=("stage", "field"),
        domain=WALL,
    )
    row = stage.telemetry()
    for fld, v in sorted(row.items()):
        if fld in ("beta", "queue"):
            sgauge.set(v, stage=stage.name, field=fld)
        elif v:
            sev.inc(v, stage=stage.name, kind=fld)
    qids = sorted(query_ids) if query_ids is not None else stage.query_ids()
    if qids:
        qev = registry.counter(
            "repro_stage_query_events_total",
            "Serving-stage per-query telemetry counters (same row shape as "
            "the stage-wide sample).",
            labels=("stage", "query", "kind"),
            domain=WALL,
        )
        qgauge = registry.gauge(
            "repro_stage_query_row",
            "Serving-stage per-query budget/queue sample.",
            labels=("stage", "query", "field"),
            domain=WALL,
        )
        for qid in qids:
            qrow = stage.telemetry(query_id=qid)
            for fld, v in sorted(qrow.items()):
                if fld in ("beta", "queue"):
                    qgauge.set(v, stage=stage.name, query=str(qid), field=fld)
                elif v:
                    qev.inc(v, stage=stage.name, query=str(qid), kind=fld)
    return registry


def collect_dispatch(registry: MetricsRegistry) -> MetricsRegistry:
    """Kernel-plane profile: call/compile counters and jit cache occupancy
    (WALL: backend-dependent).  Host time inside the dispatch entry points
    is in the ``repro.reid.*`` spans of a JAX profiler trace."""
    from repro.kernels import dispatch

    stats = dispatch.stats()
    calls = registry.counter(
        "repro_kernel_calls_total",
        "Padded-kernel dispatches by entry point.",
        labels=("kernel",),
        domain=WALL,
    )
    for kind in ("reid_calls", "reid_multi_calls", "ball_calls"):
        if stats.get(kind):
            calls.inc(stats[kind], kernel=kind.rsplit("_calls", 1)[0])
    cache = registry.counter(
        "repro_kernel_device_cache_events_total",
        "Device-resident gallery cache hits/misses.",
        labels=("event",),
        domain=WALL,
    )
    if stats.get("device_cache_hits"):
        cache.inc(stats["device_cache_hits"], event="hit")
    if stats.get("device_cache_misses"):
        cache.inc(stats["device_cache_misses"], event="miss")
    profile = dispatch.profile()
    compiles = registry.counter(
        "repro_kernel_compiles_total",
        "Distinct padded bucket shapes compiled, per kernel entry point "
        "(each new shape is one XLA compile).",
        labels=("kernel",),
        domain=WALL,
    )
    for kernel, n in sorted(profile["compiles"].items()):
        if n:
            compiles.inc(n, kernel=kernel)
    sizes = registry.gauge(
        "repro_jit_cache_entries",
        "Entries currently held by each bounded jit cache.",
        labels=("cache",),
        domain=WALL,
    )
    for name, n in sorted(dispatch.jit_cache_sizes().items()):
        sizes.set(n, cache=name)
    return registry
