"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The window is the host annotation ``bench.window``.  Inside it:

* ``busy_s`` — the union of the intervals in which an op of the device's
  ``XLA Ops`` line ran, averaged over the device planes;
* ``module_s`` / ``module_calls`` — device time and count of each XLA
  module (one jitted program), by its name without the hash;
* ``idle_gaps`` — the device's idle time split by what the host's main
  thread was doing: the innermost ``bench.*`` annotation open at each
  instant, ``other`` where none is.

Only ``jax.profiler.ProfileData`` is used, so no TensorFlow is needed.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."
_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def clip_spans(spans: List[Tuple[float, float, str]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi), n) for s, e, n in spans if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def host_states(spans: List[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Flatten properly nested host spans into disjoint segments, each
    named by the innermost span open there."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    out: List[Tuple[float, float, str]] = []
    open_: List[Tuple[float, float, str]] = []  # in order of start
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        if open_:
            # The latest-starting open span is the innermost.
            out.append((a, b, open_[-1][2]))
    return out


def attribute(idle: List[Interval], states: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of ``idle`` spent in each host state (``other`` if none)."""
    starts = [s for s, _, _ in states]
    acc: Dict[str, float] = {}
    for g0, g1 in idle:
        covered = 0.0
        j = max(bisect.bisect_right(starts, g0) - 1, 0)
        while j < len(states) and states[j][0] < g1:
            s, e, name = states[j]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                acc[name] = acc.get(name, 0.0) + ov
                covered += ov
            j += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            acc["other"] = acc.get("other", 0.0) + rest
    return acc


def reduce_trace(path: str) -> Optional[Dict]:
    """The reduced trace, or None when it holds no ``bench.window``.
    Times are in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window: Optional[Interval] = None
    spans: List[Tuple[float, float, str]] = []
    devices: List[Tuple[List[Interval], Dict[str, List[Interval]]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops: List[Interval] = []
            modules: Dict[str, List[Interval]] = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.setdefault(_HASH.sub("", e.name), []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
            if ops or modules:
                devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        iv = (e.start_ns, e.start_ns + e.duration_ns)
                        if e.name == WINDOW:
                            window = iv
                        else:
                            spans.append((iv[0], iv[1], e.name))
    if window is None:
        return None
    lo, hi = window
    ns = 1e-9
    busy_each, module_s, module_calls = [], {}, {}
    idle_acc: Dict[str, float] = {}
    states = host_states(clip_spans(spans, lo, hi))
    for ops, modules in devices:
        busy = union(clip(ops, lo, hi))
        busy_each.append(sum(e - s for s, e in busy) * ns)
        for name, ivs in modules.items():
            inside = clip(ivs, lo, hi)
            if inside:
                module_s[name] = module_s.get(name, 0.0) + sum(e - s for s, e in inside) * ns
                module_calls[name] = module_calls.get(name, 0) + len(inside)
        for name, s in attribute(gaps(busy, lo, hi), states).items():
            idle_acc[name] = idle_acc.get(name, 0.0) + s * ns
    n = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy_each) / n,
        "devices": len(devices),
        "module_s": module_s,
        "module_calls": module_calls,
        "idle_by_host": {k: v / n for k, v in idle_acc.items()},
    }


def breakdown(reduced: Dict, top: int = 10) -> Dict[str, List]:
    """The result line's ``breakdown``: the device programs that took most
    time and the idle time by host state, each at most ``top`` entries."""
    ops = sorted(reduced["module_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced["idle_by_host"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
