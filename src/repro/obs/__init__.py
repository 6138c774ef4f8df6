"""Unified observability plane: metrics registry, span tracing, exporters.

Two kinds of span: ``EventTracer`` follows sampled events through the
pipeline in simulated time; ``span`` (from ``repro.core.clock``, where hot
paths import it) marks host work at each layer boundary in any JAX
profiler trace of a run, on the device trace's clock.  ``SPANS`` and
``MODULE_SPAN`` name every host span the platform emits.

Layering: ``repro.obs`` may import any other repro package (it observes
them); nothing on a hot path imports ``repro.obs`` — the pipeline's
tracer hooks are duck-typed and default to ``None``.

    from repro.obs import MetricsRegistry, EventTracer, collect_query_result
    reg = MetricsRegistry()
    scn = MultiQueryScenario(cfg, specs)
    res = scn.run()
    collect_query_result(reg, scn, res)
    print(reg.exposition())          # Prometheus text format
    print(reg.digest())              # sha256 of the SIM-domain exposition
"""

from repro.core.clock import MODULE_SPAN, SPANS, span
from repro.obs.collectors import (
    collect_dispatch,
    collect_engine,
    collect_journal,
    collect_query_result,
    collect_scenario,
    collect_stage,
)
from repro.obs.export import (
    exposition_digest,
    metrics_jsonl,
    prometheus_exposition,
    spans_jsonl,
    write_text,
)
from repro.obs.health import healthz, probe_backend, probe_journal, probe_stage, readyz
from repro.obs.metrics import (
    REGISTRY,
    SIM,
    WALL,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.tracing import EventTracer, Span, transit_class

__all__ = [
    "REGISTRY",
    "SIM",
    "WALL",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "EventTracer",
    "Span",
    "span",
    "SPANS",
    "MODULE_SPAN",
    "transit_class",
    "collect_scenario",
    "collect_query_result",
    "collect_journal",
    "collect_stage",
    "collect_dispatch",
    "collect_engine",
    "prometheus_exposition",
    "exposition_digest",
    "metrics_jsonl",
    "spans_jsonl",
    "write_text",
    "healthz",
    "readyz",
    "probe_stage",
    "probe_journal",
    "probe_backend",
]
