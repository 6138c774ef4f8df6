"""Sweep engine: run a whole grid of scenario configs — or compiled apps —
in one pass.

A benchmark sweep (the paper's Figs. 5-13) is a list of ``(name, case)``
pairs where ``case`` is either a plain ``ScenarioConfig`` (the preset app)
or an :class:`AppCase` pairing a ``TrackingApp`` factory + ``DeploymentSpec``
with a workload config — so all four Table-1 apps run through the same
engine, lowered by ``repro.core.compile.compile_app``.  :class:`SweepRunner`
executes the grid with the shared-world machinery:

* distinct :class:`~repro.sim.world.WorldKey`\\ s are prebuilt **once** in
  the parent and attached to the configs, so no grid point rebuilds
  geometry it shares with another;
* on platforms with ``fork`` the configs run concurrently in a process
  pool — the prebuilt worlds are inherited copy-on-write, and configs are
  indexed through a module-level list so grids carrying unpicklable
  members (e.g. a ``bandwidth_schedule`` lambda) still work;
* everywhere else (or with ``mode="serial"``) the grid runs serially in
  process, producing the **same records**.

Every scenario is self-contained — its RNG streams derive only from its
own config seed and its world is deterministic in its key — so each
per-config ``summary()`` is bit-identical between serial and concurrent
execution, and to a plain sequential ``TrackingScenario(cfg).run()``.

Workers disable the cyclic GC around ``run()`` (the event runtime is
allocation-lean and acyclic; collection pauses only add wall-clock noise);
results carry construction and run wall-times separately.
"""

from __future__ import annotations

import gc
import multiprocessing
import os
import sys
import time
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

from .scenario import ScenarioConfig, TrackingScenario
from .world import WorldKey, clear_world_cache, get_world, world_cache_stats

__all__ = ["AppCase", "CaseRecord", "QueryCase", "SweepResult", "SweepRunner"]


@dataclass
class AppCase:
    """One app-grid point: a ``TrackingApp`` (or factory) + deployment over
    a workload.

    ``app`` is either a :class:`~repro.core.dataflow.TrackingApp` or a
    factory ``(world, cameras) -> TrackingApp`` — grids prefer factories so
    fork workers construct JAX-touching apps (towers, kernels) inside their
    own process, and so each case's TL strategy gets its own instance bound
    to the case's world geometry.  ``workload`` is a ``ScenarioConfig``
    describing cameras/duration/walk/QoS; its module knobs (``num_va``,
    ``batching``, costs...) are ignored in favor of the app's specs merged
    over ``deployment``.  ``needs_jax`` routes auto-mode grids away from
    fork pools (see ``SweepRunner._resolve_mode``).
    """

    app: object  # TrackingApp | (world, cameras) -> TrackingApp
    workload: ScenarioConfig
    deployment: Optional[object] = None  # DeploymentSpec | None -> workload's
    needs_jax: bool = False


@dataclass
class QueryCase:
    """One multi-query grid point: N concurrent tracking queries (an int or
    a sequence of ``repro.query.QuerySpec``) fused over one shared pipeline
    on ``workload``, optionally behind an admission policy/controller.

    Runs through ``repro.query.MultiQueryScenario`` (imported lazily so the
    sweep engine has no hard dependency on the tenancy plane); the record's
    summary is the fused run's global summary plus the per-query extras
    (``queries``, ``union_peak_active``, admission counters...).
    """

    queries: object  # int | Sequence[repro.query.QuerySpec]
    workload: ScenarioConfig
    admission: Optional[object] = None  # AdmissionPolicy | AdmissionController
    spotlight_mode: str = "per-query"


@dataclass
class CaseRecord:
    """Per-config result: the summary plus split wall-times (picklable)."""

    name: str
    summary: Dict
    build_s: float  # scenario construction (world fetch + pipeline build)
    run_s: float  # TrackingScenario.run() only
    world_build_s: float  # non-zero only when this case built its world
    seed: int

    @property
    def us_per_event(self) -> float:
        return self.run_s * 1e6 / max(self.summary.get("source_events", 0), 1)


@dataclass
class SweepResult:
    records: List[CaseRecord]
    wall_s: float  # whole-sweep wall-clock (world prebuild + all cases)
    mode: str  # "fork" | "serial"
    workers: int
    worlds_built: int
    world_build_s: float


def _workload(case) -> ScenarioConfig:
    """The ScenarioConfig a grid entry runs over (identity for plain
    configs, the embedded workload for app/query cases)."""
    return case.workload if isinstance(case, (AppCase, QueryCase)) else case


def _touches_jax(case) -> bool:
    """Whether a grid case dispatches work to a JAX device: re-ID
    embeddings, the fused mega-step engine, kernel spotlights, or an app
    declared ``needs_jax``."""
    cfg = _workload(case)
    return (
        cfg.embed_dim > 0
        or cfg.engine == "megastep"
        or (isinstance(case, AppCase) and case.needs_jax)
        or (isinstance(case, QueryCase) and case.spotlight_mode == "kernel")
    )


def _jax_initialized() -> bool:
    """Whether this process has brought up a JAX backend (and with it the
    XLA threads and, on a TPU host, the chip)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _run_case(name: str, case) -> CaseRecord:
    t0 = time.perf_counter()
    if isinstance(case, QueryCase):
        from repro.query import MultiQueryScenario

        scenario = MultiQueryScenario(
            case.workload,
            case.queries,
            admission=case.admission,
            spotlight_mode=case.spotlight_mode,
        )
        cfg = case.workload
    elif isinstance(case, AppCase):
        scenario = TrackingScenario(
            case.workload, app=case.app, deployment=case.deployment
        )
        cfg = case.workload
    else:
        scenario = TrackingScenario(case)
        cfg = case
    build_s = time.perf_counter() - t0
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        t0 = time.perf_counter()
        result = scenario.run()
        run_s = time.perf_counter() - t0
    finally:
        if gc_was_enabled:
            gc.enable()
    return CaseRecord(
        name=name,
        summary=result.summary(),
        build_s=build_s,
        run_s=run_s,
        world_build_s=scenario.world_build_seconds,
        seed=cfg.seed,
    )


# Fork-inherited grid: worker processes index into this instead of having
# cases pickled to them (configs and apps may carry lambdas/towers, and the
# attached WorldBundles travel copy-on-write through fork for free).
_ACTIVE_GRID: List[Tuple[str, object]] = []


def _run_case_at(idx: int) -> CaseRecord:
    name, cfg = _ACTIVE_GRID[idx]
    return _run_case(name, cfg)


def _cost_hint(case) -> float:
    """Rough relative cost of a case, used only to order pool submission
    (longest first minimizes makespan).  Source events dominate: a base TL
    sources every camera each tick; spotlight TLs source an active set that
    grows with the entity peak speed.  App cases are estimated from their
    workload (the app's own TL strategy isn't constructed until the worker
    builds the world)."""
    cfg = _workload(case)
    ticks = cfg.duration_s * cfg.fps
    dyn = getattr(cfg, "dynamism", None)
    if dyn is not None:
        # Input-rate spikes multiply the source tick count over their
        # window — the actual cost driver for dynamism grid points.
        for p in dyn.perturbations:
            if hasattr(p, "rate_multiplier") and hasattr(p, "window"):
                s, e = p.window()
                s = max(0.0, min(s, cfg.duration_s))
                e = min(e, cfg.duration_s)
                if e > s:
                    ticks += (p.rate_multiplier((s + e) / 2.0) - 1.0) * (e - s) * cfg.fps
    if cfg.tl == "base":
        per_tick = float(cfg.num_cameras)
    else:
        per_tick = 3.0 * cfg.tl_peak_speed**2
    overload = 2.0 if cfg.drops_enabled else 1.0
    return ticks * per_tick * overload


class SweepRunner:
    """Executes a grid of scenario configs with shared worlds.

    ``mode``: ``"auto"`` picks a fork pool when the platform supports it
    and the grid has more than one case, else serial; ``"fork"`` forces
    the pool; ``"serial"`` runs in process.  ``share_worlds=False``
    disables world prebuilding *and* clears the world/road caches before
    every case — the faithful "rebuild everything per config" sequential
    baseline the sweep engine is measured against.
    """

    def __init__(
        self,
        mode: str = "auto",
        max_workers: Optional[int] = None,
        share_worlds: bool = True,
    ) -> None:
        if mode not in ("auto", "fork", "serial"):
            raise ValueError(f"unknown sweep mode {mode!r}")
        if mode == "fork" and not share_worlds:
            raise ValueError(
                "share_worlds=False is the sequential cold baseline; "
                "it cannot run in a fork pool"
            )
        self.mode = mode
        self.max_workers = max_workers
        self.share_worlds = share_worlds

    # ------------------------------------------------------------------ #
    @staticmethod
    def fork_available() -> bool:
        return "fork" in multiprocessing.get_all_start_methods()

    def _resolve_mode(self, n_cases: int, needs_jax: bool = False) -> Tuple[str, int]:
        workers = self.max_workers or os.cpu_count() or 1
        workers = max(1, min(workers, n_cases))
        if self.mode == "fork":
            # Forced pool: never degrade silently (a 1-worker pool is still
            # a fork pool — results must be identical either way).
            if not self.fork_available():
                raise RuntimeError("fork start method unavailable on this platform")
            if needs_jax and _jax_initialized():
                # A forked child inherits the parent's XLA threads and, on a
                # TPU host, its claim on the chip: the child hangs or fails.
                raise RuntimeError(
                    "this grid dispatches to a JAX device and JAX is already "
                    "initialised in this process: forking it would share "
                    "the device; run it with mode='serial'"
                )
            return "fork", workers
        if self.mode == "serial" or workers == 1 or not self.fork_available():
            return "serial", 1
        if needs_jax:
            # JAX (multithreaded XLA) in a forked child of a JAX-initialized
            # parent can deadlock, and a chip takes one process: grids whose
            # scenarios dispatch to a device run serially unless fork is
            # forced.
            return "serial", 1
        return "fork", workers

    # ------------------------------------------------------------------ #
    def run(self, grid: Sequence[Tuple[str, object]]) -> SweepResult:
        grid = list(grid)
        t_sweep = time.perf_counter()
        builds_before = world_cache_stats()["builds"]
        world_build_s = 0.0
        if self.share_worlds and grid:
            # Prebuild each distinct world once (deduplicated by key) and
            # attach the bundle so no case rebuilds shared geometry.
            bundles: Dict[WorldKey, object] = {}
            attached = []
            for name, case in grid:
                cfg = _workload(case)
                if cfg.world is not None:
                    attached.append((name, case))
                    continue
                key = WorldKey.from_config(cfg)
                bundle = bundles.get(key)
                if bundle is None:
                    t0 = time.perf_counter()
                    bundle = get_world(key)
                    world_build_s += time.perf_counter() - t0
                    bundles[key] = bundle
                cfg = replace(cfg, world=bundle)
                if isinstance(case, (AppCase, QueryCase)):
                    case = replace(case, workload=cfg)
                else:
                    case = cfg
                attached.append((name, case))
            grid = attached
        # True builds only: LRU/disk hits during the prebuild don't count.
        worlds_built = world_cache_stats()["builds"] - builds_before
        world_build_total = world_build_s
        needs_jax = any(_touches_jax(case) for _, case in grid)
        if not self.share_worlds:
            # The cold baseline is by definition sequential (per-case cache
            # clearing cannot be meaningful across concurrent workers).
            mode, workers = "serial", 1
        else:
            mode, workers = self._resolve_mode(len(grid), needs_jax=needs_jax)
        if mode == "fork":
            records = self._run_fork(grid, workers)
        elif self.share_worlds:
            records = [_run_case(name, cfg) for name, cfg in grid]
        else:
            # Cold baseline: every config rebuilds its world from scratch —
            # in-memory caches cleared per case AND the on-disk cache masked
            # (benchmarks default it on; a disk hit would warm the baseline).
            from repro.core.roadnet import clear_network_cache

            disk_env = os.environ.get("REPRO_WORLD_CACHE")
            os.environ["REPRO_WORLD_CACHE"] = "0"
            try:
                records = []
                for name, cfg in grid:
                    clear_world_cache()
                    clear_network_cache()
                    records.append(_run_case(name, cfg))
            finally:
                if disk_env is None:
                    del os.environ["REPRO_WORLD_CACHE"]
                else:
                    os.environ["REPRO_WORLD_CACHE"] = disk_env
            # Cold mode: every case built its own world; the per-case
            # clearing also reset the global stats, so report from records.
            worlds_built = len(records)
            world_build_total = sum(r.world_build_s for r in records)
        return SweepResult(
            records=records,
            wall_s=time.perf_counter() - t_sweep,
            mode=mode,
            workers=workers,
            worlds_built=worlds_built,
            world_build_s=world_build_total,
        )

    def _run_fork(
        self, grid: List[Tuple[str, object]], workers: int
    ) -> List[CaseRecord]:
        global _ACTIVE_GRID
        ctx = multiprocessing.get_context("fork")
        prev, _ACTIVE_GRID = _ACTIVE_GRID, grid
        # Longest-expected-first submission (with chunksize=1) minimizes the
        # makespan when the grid mixes heavy and light cases; the records
        # are restored to grid order below, so output is order-stable.
        order = sorted(
            range(len(grid)), key=lambda i: -_cost_hint(grid[i][1])
        )
        try:
            with ctx.Pool(processes=workers) as pool:
                out = pool.map(_run_case_at, order, chunksize=1)
        finally:
            _ACTIVE_GRID = prev
        records: List[Optional[CaseRecord]] = [None] * len(grid)
        for pos, idx in enumerate(order):
            records[idx] = out[pos]
        return records  # type: ignore[return-value]
