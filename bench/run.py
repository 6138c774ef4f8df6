"""Run one benchmark cell once on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its deployment and its query mix are found by name from
``BENCHMARK.json`` (``bench/workload.py``).  A run is a closed loop: after
set-up (JAX and the chip, the world from its disk cache, every re-ID shape
the mix uses compiled or loaded from the compile cache) it replays the
deployment back to back, one replay at a time, and the window ends with the
first replay that finishes after ``--seconds``.

* A DES cell (``"engine": "interpreted"``) steps each replay with
  ``MultiQueryScenario.run_until``, one frame period at a time, and drains
  it with ``run()``; the window closes at the first frame tick reached
  after ``--seconds``, inside the replay then running.
* An engine cell (``"engine": "megastep"``) runs each replay with
  ``MultiQueryScenario(...).run()``, the one entry the fused engine takes;
  the window closes with the first replay that finishes after ``--seconds``.

``feeds_per_chip`` is cameras times the simulated seconds the window
advanced, per second of window and per chip: the camera feeds one chip
tracks in real time.  With ``--trace 1`` the window is traced and the
per-layer metrics are read from the trace and the counters
(``bench/metrics/<name>.py``).

After the window, the deployment's plain reference and the float64 re-ID
reference decide ``correct`` with the comparisons of ``bench/check.py``:
every replay of the window, the last one as far as the window took it.  A
configuration names its reference with ``"reference": "<name>"``, the
module ``bench/<name>.py`` (``bench/refsim.py`` where it names none), which
offers ``refuses(config, plans)`` and ``books(config, plans, cuts, *,
time32=False)``.  The run fails, printing no result, where the reference
refuses the cell (before anything is warmed up), or where JAX finds no TPU
or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

from . import check, workload  # noqa: E402
from .workload import BENCH_DIR, ROOT  # noqa: E402

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


class Refused(RuntimeError):
    """The configuration's reference does not simulate the cell."""


# --------------------------------------------------------------------- #
# Compile accounting, from JAX's own compile events                      #
# --------------------------------------------------------------------- #
class Compiles:
    """Count and seconds of XLA programs compiled or loaded from the
    persistent cache (JAX records both as a backend compile), and the
    cache's hits."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def __call__(self, event: str, secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1


# --------------------------------------------------------------------- #
# The re-ID tap: every dispatch of the window, kept for the comparison   #
# --------------------------------------------------------------------- #
class ReidTap:
    """Stands in for ``dispatch.reid_match_multi``: calls ``fn`` and keeps
    each call's operands and answers, tagged with the replay it served.
    Answers are copied to the host as they come, and the device arrays let
    go every ``HOLD`` calls, so the tap holds no device memory to speak of."""

    HOLD = 64

    def __init__(self, fn: Callable, annotate) -> None:
        self.fn = fn
        self.annotate = annotate
        self.calls: List[list] = []
        self.replay = -1
        self.recording = False
        self._held = 0

    def __call__(self, gallery, queries, *, mask=None, threshold=0.5):
        with self.annotate("bench.reid"):
            scores, matched = self.fn(gallery, queries, mask=mask, threshold=threshold)
        if self.recording:
            for a in (scores, matched):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            self.calls.append([self.replay, gallery, queries, mask, threshold,
                               scores, matched])
            self._held += 1
            if self._held > self.HOLD:
                self.to_host(keep=1)
        return scores, matched

    def to_host(self, keep: int = 0) -> None:
        """Swap the held device answers for host arrays, all but the newest
        ``keep`` (the caller has not read those yet)."""
        import numpy as np

        n = len(self.calls)
        for c in self.calls[n - self._held:n - keep]:
            c[5], c[6] = np.asarray(c[5]), np.asarray(c[6])
        self._held = keep


def _load(modname: str, path: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    return _load(f"bench.metrics.{name}", os.path.join(BENCH_DIR, "metrics", name + ".py")).read


REFERENCE_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def load_reference(config: Dict[str, Any]):
    """The reference module that ``config`` names (``refsim`` where it
    names none), loaded from ``bench/<name>.py``."""
    name = config.get("reference", "refsim")
    path = os.path.join(BENCH_DIR, f"{name}.py")
    if not REFERENCE_NAME.fullmatch(name) or not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config.get('name')!r} names the reference "
                                f"{name!r}, but bench/{name}.py does not exist")
    return _load(f"bench.{name}", path)


def cell_metrics(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in moves)]


# --------------------------------------------------------------------- #
# One replay                                                            #
# --------------------------------------------------------------------- #
def replay(cell: workload.Cell, cfg, specs, annotate, stop=None):
    """Run the deployment once through the cell's timed entry.

    Returns ``(result, scenario, t)``: ``t`` is the simulated time reached.
    A DES replay asks ``stop()`` after each frame period and, where it says
    so, is left there with ``result`` None."""
    from repro.query import MultiQueryScenario

    with annotate("bench.replay_setup"):
        scn = MultiQueryScenario(cfg, specs)
    if cell.engine == "megastep":
        with annotate("bench.engine"):
            return scn.run(), scn, cfg.duration_s
    period = 1.0 / cfg.fps
    k = 1
    while k * period <= cfg.duration_s:
        with annotate("bench.des"):
            scn.run_until(k * period)
        if stop is not None and stop():
            return None, scn, k * period
        k += 1
    with annotate("bench.des"):
        return scn.run(), scn, cfg.duration_s


def warm_up(cell: workload.Cell, cfg, specs) -> None:
    """Compile (or load from the cache) every program the window runs.

    Re-ID: each VA batch of N rows against the Q live queries' embeddings
    dispatches the padded matcher and slices its (N, Q) answer, so each N
    a batch can have (one frame with drops off, where every batch is a
    single frame; up to ``m_max`` with drops on) is warmed for each
    live-query count the mix can have.  The engine: one whole replay."""
    import numpy as np

    from repro.kernels import dispatch

    if cfg.embed_dim:
        n = len(specs)
        fixed = all(s.submit_at <= 0 and s.ttl_s is None and s.cancel_at is None
                    for s in specs)
        for q in ([n] if fixed else range(1, n + 1)):
            block = np.ones((q, cfg.embed_dim), np.float32)
            for rows in range(1, (cfg.m_max if cfg.drops_enabled else 1) + 1):
                _, matched = dispatch.reid_match_multi(
                    np.ones((rows, cfg.embed_dim), np.float32), block,
                    mask=np.ones((rows, q), bool), threshold=cfg.reid_threshold)
                np.asarray(matched)
    if cell.engine == "megastep":
        replay(cell, cfg, specs, contextlib.nullcontext)


def cell_config(cell: workload.Cell, override: Dict[str, Any]) -> Dict[str, Any]:
    """The cell's configuration with ``override`` on its scenario keys."""
    return dict(cell.config, scenario=dict(cell.config["scenario"], **override))


def reference_books(cell: workload.Cell, override: Dict[str, Any], plans, cuts, *,
                    time32: bool = False):
    """The configuration's reference books at each simulated time of
    ``cuts`` (the horizon for a finished replay); ``time32`` asks for its
    float32-time control."""
    config = cell_config(cell, override)
    return load_reference(config).books(config, plans, cuts, time32=time32)


# --------------------------------------------------------------------- #
# One run                                                               #
# --------------------------------------------------------------------- #
def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_tpu: bool = True,
    override: Optional[Dict[str, Any]] = None,
    matcher: Optional[Callable] = None,
    fault: Optional[Callable] = None,
    whole: bool = False,
    log: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """Run cell ``name`` once and return its result line as a dict.

    ``override`` changes deployment keys (small CPU runs), ``matcher``
    replaces the re-ID dispatch (the precision control), ``fault`` is a
    context manager factory that breaks the timed path under the window
    (the self-tests), and ``whole`` closes the window with a finished
    replay (the readings); the benchmark's own runs use none of them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.environ["REPRO_WORLD_CACHE"] = os.path.join(CACHE_DIR, "worlds")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    sys.path.insert(0, os.path.join(ROOT, "src"))
    bench = workload.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = workload.load_cell(name)
    plans = workload.query_plans(cell, seed)
    config = cell_config(cell, override or {})
    reference = load_reference(config)
    reason = reference.refuses(config, plans)
    if reason is not None:
        raise Refused(f"cell {name}: {reason}")

    import jax
    import numpy as np

    # Every program, however quick to compile, goes to the cache in the
    # checkout, which grows without eviction.
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.event)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    from repro.kernels import dispatch
    from repro.sim import WorldKey, get_world

    cfg = workload.scenario_config(cell, **(override or {}))
    specs = workload.query_specs(plans)
    get_world(WorldKey.from_config(cfg))
    annotate = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    real = dispatch.reid_match_multi
    tap = ReidTap(matcher or real, annotate)
    dispatch.reid_match_multi = tap
    try:
        warm_up(cell, cfg, specs)
        setup_s = time.perf_counter() - T_START
        log(f"setup: {setup_s:.3f} s, {compiles.count} programs compiled or "
            f"loaded in {compiles.seconds:.3f} s, {compiles.cache_hits} from the cache")

        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the bench.* annotations, not the runtime's
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        dispatch.reset_stats()
        c0 = compiles.count
        tap.recording = True
        results = []
        with (fault() if fault else contextlib.nullcontext()):
            with annotate("bench.window"):
                t0 = time.perf_counter()

                def stop() -> bool:
                    return time.perf_counter() - t0 >= seconds

                while True:
                    tap.replay = len(results)
                    results.append(replay(cell, cfg, specs, annotate,
                                          None if whole else stop))
                    if results[-1][0] is None or stop():
                        break
                window_s = time.perf_counter() - t0
        tap.recording = False
        tap.to_host()
        compiles_in_window = compiles.count - c0
        counters = dispatch.stats()
        if trace:
            jax.profiler.stop_trace()
    finally:
        dispatch.reid_match_multi = real
    chips = devices[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in chips)

    replays = [{"feeds": cfg.num_cameras * t,
                "ticks": len(scn._stats_active),
                "lit": sum(c for _, c in scn._stats_active),
                "engine_used": getattr(scn, "engine_used", "interpreted"),
                "fallback": getattr(scn, "engine_fallback_reason", "")}
               for _, scn, t in results]
    for r in sorted({(r["engine_used"], r["fallback"]) for r in replays}):
        log(f"engine: used={r[0]} fallback={r[1]!r}")
    log(f"window: {len(results)} replays to t={[t for _, _, t in results]} in "
        f"{window_s:.3f} s, {compiles_in_window} compiles, "
        f"{counters['reid_multi_calls']} re-ID dispatches")

    # ---- correct: the references once the window has closed ------------ #
    t_ref = time.perf_counter()
    horizon = cfg.duration_s + 3.0 * cfg.gamma
    cut = [horizon if res is not None else t for res, _, t in results]
    want = reference.books(config, plans, cut)
    log(f"reference: simulated in {time.perf_counter() - t_ref:.3f} s")
    bad = set()
    first = None
    gap = 0.0
    for i, ((res, scn, _), t) in enumerate(zip(results, cut)):
        got = check.observe_platform(scn, res, [c for c in tap.calls if c[0] == i])
        d, g = check.compare_books(got, want[t])
        gap = max(gap, g)
        if d is not None or g > check.LIMITS["latency_gap_s"]:
            bad.add(i)
            first = first or d
    numbers: Dict[str, float] = {"replays_differing": float(len(bad)),
                                 "latency_gap_s": gap}
    if cfg.embed_dim:
        reid = check.reid_compare(tap.calls)
        bad |= reid.pop("bad_replays")
        numbers.update(reid)
    correct, checks = check.verdict(numbers)
    log(f"reference: compared in {time.perf_counter() - t_ref:.3f} s, first "
        f"difference {check.describe_diff(first)}, "
        f"{numbers.get('reid_pairs_checked', 0)} re-ID pairs checked")

    # ---- metrics -------------------------------------------------------- #
    kind = "per_layer" if trace else "end_to_end"
    values = {"feeds_per_chip": sum(r["feeds"] for r in replays) / window_s / cell.chips,
              "setup_s": setup_s}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(results),
                           "failed": len(bad)}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        from . import kernels
        from .trace import breakdown as make_breakdown, find_xplane, reduce_trace

        reduced = reduce_trace(find_xplane(TRACE_DIR))
        record = {
            "trace": reduced,
            "replays": replays,
            "engine": cell.engine,
            "counters": counters,
            "compiles_in_window": compiles_in_window,
            "reid_shapes": [(np.shape(c[1])[0], np.shape(c[2])[0], np.shape(c[1])[1])
                            for c in tap.calls],
            "peaks": kernels.peaks(dev.device_kind) if require_tpu else None,
        }
        for m in cell_metrics(bench, name, kind):
            v = load_metric(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = make_breakdown(reduced)
    else:
        for m in cell_metrics(bench, name, kind):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, Refused) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
