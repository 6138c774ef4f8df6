"""Device clock model with skew (paper §4.6.2).

Devices on a MAN/WAN have unsynchronized clocks.  Anveshak's decisions are
designed so that, as long as the *source* and *sink* clocks agree
(kappa_1 == kappa_n), a constant per-device skew ``sigma_i = kappa_i - kappa_1``
cancels out of every drop and batch comparison.  We model that skew explicitly
so the property tests can verify the cancellation.

The module also owns host timing: :func:`monotonic` for wall-time reads
and :func:`span` for the host spans a JAX profiler trace records.
"""

from __future__ import annotations

import contextlib
import sys
import time as _time
from dataclasses import dataclass

__all__ = ["Clock", "monotonic", "span", "SPANS", "MODULE_SPAN"]

#: Every host span the platform emits, besides ``MODULE_SPAN + <module>``.
SPANS = (
    "repro.des.run",  # TrackingScenario.run_until: the event loop to t, VA's late re-ID reads
    "repro.des.drain",  # TrackingScenario.run: the event loop to the horizon, the same reads
    "repro.tl.tick",  # one TL tick: spotlights and control deltas
    "repro.va.reid_build",  # VA re-ID: gallery stack and tenancy mask
    "repro.va.reid_wait",  # VA re-ID: reading one answer, the queue's flush when it is the first
    "repro.reid.dispatch",  # dispatch.reid_match_multi, whole: the call queued
    "repro.reid.prep",  # validation, copies, device-resident query lookup
    "repro.reid.flush",  # dispatch.reid_flush: every queued call answered
    "repro.reid.call",  # one launch of the jitted matcher, host operands' transfer included
    "repro.reid.slice",  # the launch's answer read to the host and cut per call
    "repro.drop.exec",  # Task._maybe_run: drop point 2 over a formed batch (drops on)
    "repro.batch.timer",  # Task._timer_fire: the dynamic batcher's auto-submit
    "repro.budget.reject",  # Task._on_drop: reject signals upstream, and the probe
    "repro.budget.accept",  # SinkTask._send_accept: accept signals upstream
)
#: Prefix of a module instance's user-logic span: ``task.module or task.name``.
MODULE_SPAN = "repro.module."

_NO_SPAN = contextlib.nullcontext()


def monotonic() -> float:
    """Process-local monotonic clock for *measuring* wall time (benchmark
    and log timings).

    Every host-side timing read in the tree routes through here: simulation
    time comes from the DES, and raw ``time.time()`` reads are flagged by
    the replay-safety analyzer (DET002) because a wall-clock read inside
    decision logic is a determinism leak.  ``perf_counter`` is monotonic
    and unaffected by NTP steps, so elapsed-time deltas are also more
    honest than ``time.time()`` differences.
    """
    return _time.perf_counter()


def span(name: str):
    """A context manager marking a host span ``name`` in a JAX profiler
    trace, on the same clock as the device's ops.

    It reads no clock and keeps no state: where no profiler is running,
    ``jax.profiler.TraceAnnotation`` records nothing.  Where JAX has not
    been imported, no profiler can be running either, so a shared no-op
    context is returned and JAX stays unimported.
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name)


@dataclass(slots=True)
class Clock:
    """A device clock: reads true (simulation) time plus a fixed skew.

    ``now(t_true)`` is what this device's clock shows when the global
    simulation time is ``t_true``.  Durations measured on a single device are
    skew-free; only absolute timestamps carry the skew.
    """

    skew: float = 0.0

    def now(self, t_true: float) -> float:
        return t_true + self.skew
