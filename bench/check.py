"""What decides ``correct``: the comparisons with the plain references.

Nothing here imports the platform; it reads the books the platform keeps.
Four numbers, each with its limit (``LIMITS``):

* ``replays_differing`` — replays of the window (the one the window cut
  short included, as far as it got) whose books differ from those of the
  configuration's plain reference (``bench/refsim.py`` unless it names
  another) in any module's arrivals, executions, batches or drops at each
  drop point, any count, drop, state, spotlight size, camera set, re-ID
  dispatch or re-ID gallery row.  Limit 0.
* ``latency_gap_s`` — the widest gap between the platform's and the
  simulator's sink times and end-to-end latencies, and the times each query
  was found, over every replay.
* ``reid_score_gap`` — the widest gap, over every re-ID dispatch of the
  window and every (frame, query) pair it evaluated, between the score the
  timed path returned and ``reid_reference``'s float64 cosine similarity.
* ``reid_flag_mismatches`` — pairs whose match flag differs from the
  reference's where the reference's score lies farther than the score
  limit from the threshold.  Limit 0.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np

#: Limits of the compared numbers; how each was set is in PERF.md.
LIMITS = {
    "replays_differing": 0,
    "latency_gap_s": 1e-9,
    "reid_score_gap": 1e-4,
    "reid_flag_mismatches": 0,
}


# --------------------------------------------------------------------- #
# The platform's books, in the simulator's shape                          #
# --------------------------------------------------------------------- #
_COUNTS = ("sourced", "positives_generated", "completed", "on_time", "delayed",
           "positives_completed", "detections_on_time", "orphan_completed",
           "reid_matched", "dropped", "orphan_dropped")


#: ``engine_used`` of a replay the fused engine ran to its end.
ENGINE_RUNS = ("megastep-host", "megastep-device")


def _module_books(tasks) -> Dict[str, int]:
    """Drops at each point, arrivals, executions and batches, summed over
    a module's instances; the drop points first, being causes."""
    s = [t.stats for t in tasks]
    return {"dp1": sum(x.dropped_dp1 for x in s), "dp2": sum(x.dropped_dp2 for x in s),
            "dp3": sum(x.dropped_dp3 for x in s), "arrived": sum(x.arrived for x in s),
            "executed": sum(x.executed for x in s), "batches": sum(x.batches for x in s)}


def observe_platform(scn, res, calls: List[list]) -> Dict[str, Any]:
    """Books of one replay as far as it ran: ``scn`` is the platform's
    ``MultiQueryScenario``, ``res`` its result where the replay finished
    (None where the window cut it), ``calls`` the re-ID dispatches it made
    (``ReidTap`` records)."""
    base = res.result if res is not None else None
    latencies = base.latencies if base is not None else scn.sink.latencies
    g = {"source_events": scn._source_events,
         "positives_generated": scn._positives_generated,
         "positives_completed": scn._positives_completed,
         "detections_on_time": scn._detections_on_time,
         "on_time": base.on_time if base is not None else scn.sink.on_time,
         "delayed": base.delayed if base is not None else scn.sink.delayed,
         "reid_matched": base.reid_matched if base is not None else scn._reid_matched,
         "reid_dispatches": len(calls)}
    gallery = sorted(row.tobytes() for c in calls for row in np.asarray(c[1]))
    app = scn.compiled
    if getattr(scn, "engine_used", "") in ENGINE_RUNS:
        # The fused engine runs no module instance: it keeps each execution
        # as a batch size of its result, and runs drops off only.
        modules = {m: dict(dp1=0, dp2=0, dp3=0, arrived=sum(b), executed=sum(b),
                           batches=len(b)) for m, b in base.batch_sizes.items()}
    else:
        modules = {"VA": _module_books(app.va_tasks), "CR": _module_books(app.cr_tasks)}
        if app.fc_tasks:  # a fused FC stage keeps no instances
            modules = dict(FC=_module_books(app.fc_tasks.values()), **modules)
    exact = {"modules": modules, "per": {}, "global": g, "gallery": gallery,
             "timeline": list(scn._stats_active)}
    timed = {"global": sorted(latencies), "per": {}}
    for qid, st in scn.registry.states.items():
        exact["per"][qid] = dict({k: getattr(st, k) for k in _COUNTS},
                                 dp={f"dp{i}": st.dp[i] for i in (1, 2, 3)},
                                 state=st.state, ended_at=st.ended_at,
                                 found=st.found_at is not None,
                                 timeline=list(st.active_timeline),
                                 requested=sorted(st.requested),
                                 applied=sorted(st.applied))
        timed["per"][qid] = sorted(st.latencies) + (
            [(st.found_at, 0.0)] if st.found_at is not None else [])
    return {"exact": exact, "timed": timed}


def first_diff(a, b, path: str = ""):
    """Path and values of the first field where ``a`` and ``b`` differ
    (None when equal), in the order of ``a``'s keys: the books list causes
    (drops, batches) before their effects."""
    if type(a) is type(b) and isinstance(a, (dict, list, tuple)) and a == b:
        return None
    if isinstance(a, dict) and isinstance(b, dict):
        for k in list(a) + [k for k in b if k not in a]:
            if k not in a or k not in b:
                return f"{path}/{k}", a.get(k, "<missing>"), b.get(k, "<missing>")
            d = first_diff(a[k], b[k], f"{path}/{k}")
            if d is not None:
                return d
        return None
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        for i, (x, y) in enumerate(zip(a, b)):
            d = first_diff(x, y, f"{path}[{i}]")
            if d is not None:
                return d
        if len(a) != len(b):
            return f"{path}.len", len(a), len(b)
        return None
    return None if a == b else (path, a, b)


def timed_gap(a: Dict[str, Any], b: Dict[str, Any]) -> float:
    """Widest gap between two books' sorted (sink time, latency) lists; inf
    where they do not pair up."""
    pairs = [(a["global"], b["global"])]
    pairs += [(a["per"].get(q), b["per"][q]) for q in b["per"]]
    gap = 0.0
    for x, y in pairs:
        if x is None or len(x) != len(y):
            return math.inf
        if x:
            d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
            gap = max(gap, float(np.max(np.where(np.isnan(d), np.inf, d))))
    return gap


def compare_books(got: Dict[str, Any], want: Dict[str, Any]) -> Tuple[Optional[tuple], float]:
    """First exact difference (None) and the latency gap of one replay."""
    return first_diff(got["exact"], want["exact"]), timed_gap(got["timed"], want["timed"])


# --------------------------------------------------------------------- #
# Re-ID: the reference, its control, and the comparison                 #
# --------------------------------------------------------------------- #
def _unit_rows(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def reid_reference(gallery, queries, *, mask=None, threshold: float = 0.5):
    """Cosine similarity of every (gallery row, query) pair in float64, with
    the dispatch plane's contract: ``(scores, matched)`` of shape (N, Q),
    masked pairs at ``-inf`` and unmatched."""
    g = _unit_rows(np.asarray(gallery, dtype=np.float64))
    q = _unit_rows(np.asarray(queries, dtype=np.float64))
    mask = np.ones((g.shape[0], q.shape[0]), bool) if mask is None else np.asarray(mask, bool)
    sim = np.where(mask, g @ q.T, -np.inf)
    return sim, mask & (sim >= threshold)


def reid_control(gallery, queries, *, mask=None, threshold: float = 0.5):
    """``reid_reference`` one precision below what the deployment states:
    bfloat16 operands and products, float32 sums.  Never used by a run of
    the benchmark; it proves that ``reid_score_gap`` catches such a path."""
    bf16 = ml_dtypes.bfloat16

    def rnd(x):
        return np.asarray(np.asarray(x, np.float32).astype(bf16), np.float32)

    g = rnd(_unit_rows(rnd(gallery)))
    q = rnd(_unit_rows(rnd(queries)))
    mask = np.ones((g.shape[0], q.shape[0]), bool) if mask is None else np.asarray(mask, bool)
    sim = rnd(g[:, None, :] * q[None, :, :]).sum(axis=-1, dtype=np.float32)
    sim = np.where(mask, sim, -np.inf)
    return sim, mask & (sim >= threshold)


def reid_compare(calls: List[list]) -> Dict[str, Any]:
    """Compare recorded dispatches ``[replay, gallery, queries, mask,
    threshold, scores, matched]`` against ``reid_reference``."""
    gap = 0.0
    flags = 0
    pairs = 0
    bad_replays = set()
    tol = LIMITS["reid_score_gap"]
    for replay, gallery, queries, mask, thr, scores, matched in calls:
        want_s, want_m = reid_reference(gallery, queries, mask=mask, threshold=thr)
        got_s = np.asarray(scores, dtype=np.float64)
        got_m = np.asarray(matched, dtype=bool)
        m = np.asarray(mask, bool)
        if got_s.shape != want_s.shape or got_m.shape != want_m.shape:
            gap = math.inf
            bad_replays.add(replay)
            continue
        pairs += int(m.sum())
        if m.any():
            d = np.abs(got_s[m] - want_s[m])
            call_gap = float(np.max(np.where(np.isnan(d), np.inf, d)))
        else:
            call_gap = 0.0
        # An unevaluated pair must stay unmatched.
        if np.any(got_m & ~m):
            call_gap = math.inf
        clear = np.abs(want_s - thr) > tol
        call_flags = int(np.sum((got_m != want_m) & clear & m))
        gap = max(gap, call_gap)
        flags += call_flags
        if call_gap > tol or call_flags:
            bad_replays.add(replay)
    return {"reid_score_gap": gap, "reid_flag_mismatches": flags,
            "reid_pairs_checked": pairs, "bad_replays": bad_replays}


def verdict(numbers: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each compared number beside its limit."""
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]}
              for k in LIMITS if k in numbers}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def describe_diff(diff: Optional[tuple]) -> str:
    if diff is None:
        return "none"
    path, a, b = diff
    return f"{path}: {a!r} != {b!r}"[:300]
