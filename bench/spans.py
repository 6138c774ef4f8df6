"""Reduce the host spans of a traced window to what the span metrics read.

The platform marks each layer boundary of the DES, TL and re-ID dispatch
with a ``repro.*`` span (``repro.core.clock.SPANS``); the harness marks its
own work with ``bench.*``.  Both are profiler host annotations, on the
clock of the device's ``XLA Ops``.  Inside ``bench.window``, for each name:

* ``count`` and ``durations`` (seconds) of its spans;
* ``self_s``: the time in which it was the innermost open span
  (``trace.host_states``), so that the harness's work inside a program
  span (the re-ID tap inside ``repro.module.VA``) is filed under its
  ``bench.*`` name and not under the program;
* ``idle_s``: the device's idle time while it was innermost
  (``trace.gaps`` / ``trace.attribute``), averaged over the device planes.

``cover`` is the share of the ``bench.des`` + ``bench.reid`` time that
``repro.*`` spans cover.

The trace is the newest ``.xplane.pb`` under the directory ``bench/run.py``
traces to, parsed once per path and modification time for every reader.
A trace with no ``repro.*`` spans (a program without them) gives readers
nothing to read.

    python3 -m bench.spans [trace_dir]   # the last traced window, as JSON
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace
from .workload import BENCH_DIR

TRACE_DIR = os.path.join(BENCH_DIR, ".cache", "trace")
PROGRAM = "repro."
HARNESS = "bench."
NS = 1e-9

Span = Tuple[float, float, str]

_CACHE: Dict[Tuple[str, float], Optional[Dict]] = {}


def overlap(a: List[trace.Interval], b: List[trace.Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_spans(window: trace.Interval, spans: Sequence[Span],
                 devices: Sequence[List[trace.Interval]]) -> Dict:
    """Counts, durations, self time and device idle per span name inside
    ``window``; times in ns in, seconds out.  ``devices`` holds each device
    plane's op intervals."""
    lo, hi = window
    inside = trace.clip_spans(list(spans), lo, hi)
    states = trace.host_states(inside)
    durations: Dict[str, List[float]] = {}
    for s, e, name in inside:
        durations.setdefault(name, []).append((e - s) * NS)
    self_s: Dict[str, float] = {}
    for s, e, name in states:
        self_s[name] = self_s.get(name, 0.0) + (e - s) * NS
    idle_s: Dict[str, float] = {}
    for ops in devices:
        idle = trace.gaps(trace.union(trace.clip(ops, lo, hi)), lo, hi)
        for name, s in trace.attribute(idle, states).items():
            idle_s[name] = idle_s.get(name, 0.0) + s * NS / len(devices)
    harness = trace.union([(s, e) for s, e, n in inside
                           if n in ("bench.des", "bench.reid")])
    program = trace.union([(s, e) for s, e, n in inside if n.startswith(PROGRAM)])
    harness_s = sum(e - s for s, e in harness)
    return {
        "window_s": (hi - lo) * NS,
        "count": {n: len(d) for n, d in durations.items()},
        "durations": durations,
        "self_s": self_s,
        "idle_s": idle_s,
        "cover": overlap(harness, program) / harness_s if harness_s else None,
    }


def parse(path: str) -> Optional[Dict]:
    """The reduced spans of the trace at ``path``, or None where it holds
    no ``bench.window`` or no ``repro.*`` span."""
    from jax.profiler import ProfileData

    window: Optional[trace.Interval] = None
    spans: List[Span] = []
    devices: List[List[trace.Interval]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, modules = [], False
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = True
            if ops or modules:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith((PROGRAM, HARNESS)):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None or not any(n.startswith(PROGRAM) for _, _, n in spans):
        return None
    return reduce_spans(window, spans, devices)


def load(record: Optional[Dict] = None, trace_dir: Optional[str] = None) -> Optional[Dict]:
    """The reduced spans of the newest trace under ``trace_dir`` (default
    ``TRACE_DIR``); None where the run traced no window (``record["trace"]``
    empty) or the trace holds no program spans."""
    if record is not None and not record.get("trace"):
        return None
    try:
        path = trace.find_xplane(trace_dir or TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = parse(path)
    return _CACHE[key]


def count(reduced: Optional[Dict], name: str) -> int:
    return reduced["count"].get(name, 0) if reduced else 0


def total(reduced: Dict, name: str) -> float:
    """Seconds inside spans named ``name``."""
    return sum(reduced["durations"].get(name, ()))


def mean_us(reduced: Optional[Dict], name: str) -> Optional[float]:
    """Mean microseconds of the spans named ``name``; None where none ran."""
    n = count(reduced, name)
    return 1e6 * total(reduced, name) / n if n else None


def self_share(reduced: Optional[Dict], keep) -> Optional[float]:
    """Percent of the window in which a span whose name ``keep`` accepts
    was the innermost one; None where no such span ran."""
    if not reduced or not reduced["window_s"]:
        return None
    hit = [s for n, s in reduced["self_s"].items() if keep(n)]
    if not hit:
        return None
    return 100.0 * sum(hit) / reduced["window_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    reduced = load(trace_dir=argv[0] if argv else None)
    if reduced is None:
        print("bench.spans: no traced window with program spans", file=sys.stderr)
        return 1
    out = {k: v for k, v in reduced.items() if k != "durations"}
    out["total_s"] = {n: total(reduced, n) for n in reduced["durations"]}
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
