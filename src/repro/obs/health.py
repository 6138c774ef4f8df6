"""Health / readiness probes for the serving plane.

Kubernetes-style split: **readiness** means the component can take
traffic right now (stage calibrated, journal attached); **health** means
it is not degrading (runaway drop fraction, stale snapshots, a kernel
backend that died).  Probes are pure functions over the components'
existing counters — no background threads, no wall-clock reads — so
they are as deterministic as the state they inspect.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

__all__ = ["Probe", "probe_stage", "probe_journal", "probe_backend",
           "readyz", "healthz"]

#: (name, ok, detail) — the unit every aggregate reduces over.
Probe = Tuple[str, bool, str]


def probe_stage(stage, max_drop_fraction: float = 0.5) -> Probe:
    """A ServedStage is unhealthy when it sheds more than
    ``max_drop_fraction`` of its arrivals — a budget collapse the
    §4.5.2 probe machinery should have recovered from."""
    stats = getattr(stage, "stats", None)
    if not stats:
        return ("stage", False, "no stats surface")
    arrived = float(stats.get("arrived", 0))
    dropped = float(stats.get("dropped", 0))
    if arrived == 0:
        return ("stage", True, "idle")
    frac = dropped / arrived
    ok = frac <= max_drop_fraction
    return ("stage", ok, f"drop_fraction={frac:.3f}")


def probe_journal(journal, t_now: Optional[float] = None,
                  max_staleness_periods: float = 2.0) -> Probe:
    """A journal is unhealthy when its last snapshot is more than
    ``max_staleness_periods`` snapshot periods behind ``t_now`` — a
    restore would replay an unbounded tail."""
    if journal is None:
        return ("journal", False, "no journal attached")
    snapshots = getattr(journal, "snapshots", None) or []
    if not snapshots:
        # Before the first period elapses that is expected, not a failure.
        period = float(getattr(journal, "snapshot_period_s", 0.0) or 0.0)
        ok = t_now is None or period <= 0 or t_now < max_staleness_periods * period
        return ("journal", ok, "no snapshot yet")
    snap = snapshots[-1]
    if t_now is None:
        return ("journal", True, f"snapshot@t={snap['time']}")
    period = float(getattr(journal, "snapshot_period_s", 0.0) or 0.0)
    lag = t_now - float(snap["time"])
    ok = period <= 0 or lag <= max_staleness_periods * period
    return ("journal", ok, f"snapshot_lag_s={lag}")


def probe_backend() -> Probe:
    """The kernel plane is unhealthy once a device call has failed and
    forced the host-reference fallback (``ops.last_fallback_reason``); a
    refusal by design (``x64-emulated``, capacity) is not a failure."""
    from repro.kernels.megastep import ops

    reason = ops.last_fallback_reason()
    if not reason.startswith("device-error"):
        return ("backend", True, "device path clean")
    return ("backend", False, f"device fallback active: {reason}")


def _aggregate(probes: List[Probe]) -> Dict[str, object]:
    return {
        "ok": all(ok for _, ok, _ in probes),
        "components": {name: {"ok": ok, "detail": detail}
                       for name, ok, detail in probes},
    }


def readyz(stage=None, journal=None) -> Dict[str, object]:
    """Readiness: every *attached* component can take traffic.  Absent
    components are simply not probed (a stage without a journal is still
    ready — durability is an opt-in)."""
    probes: List[Probe] = []
    if stage is not None:
        xi = getattr(stage, "xi", None)
        probes.append(("stage", xi is not None, "xi calibrated" if xi else "no xi"))
    if journal is not None:
        probes.append(("journal", True, f"records={len(getattr(journal, 'records', ()))}"))
    if not probes:
        probes.append(("none", True, "nothing attached"))
    return _aggregate(probes)


def healthz(stage=None, journal=None, t_now: Optional[float] = None,
            include_backend: bool = True) -> Dict[str, object]:
    """Liveness/health over the attached components + the kernel plane."""
    probes: List[Probe] = []
    if stage is not None:
        probes.append(probe_stage(stage))
    if journal is not None:
        probes.append(probe_journal(journal, t_now=t_now))
    if include_backend:
        probes.append(probe_backend())
    return _aggregate(probes)
