"""Host spans (``repro.core.clock.span``): where the platform marks its
layer boundaries in a JAX profiler trace, and what the helper costs a
process that never imports JAX."""

import glob
import os
import subprocess
import sys

import pytest

from repro.core.clock import MODULE_SPAN, SPANS
from repro.kernels import dispatch
from repro.query import MultiQueryScenario, QuerySpec
from repro.sim import ScenarioConfig

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _parent(iv, spans, pred):
    """The innermost span matching ``pred`` that holds ``iv``."""
    held = [s for s in spans if pred(s[2]) and s is not iv and _inside(iv, s)]
    return min(held, key=lambda s: s[1] - s[0]) if held else None


#: The budget and drop plane's spans: they fire only with drops on.
DROP_PLANE = ("repro.drop.exec", "repro.batch.timer", "repro.budget.reject",
              "repro.budget.accept")


def _trace(cfg, out, ticks):
    """Two queries over ``cfg`` stepped one frame period at a time under a
    CPU profiler trace: the ``repro.*`` host spans and the dispatch count."""
    import jax
    from jax.profiler import ProfileData

    scn = MultiQueryScenario(cfg, [QuerySpec(), QuerySpec(embedding_seed=99)])
    scn.run_until(1.0)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    dispatch.reset_stats()
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for k in range(2, ticks + 1):
            scn.run_until(float(k))
    finally:
        jax.profiler.stop_trace()
    calls = dispatch.stats()["reid_multi_calls"]
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return spans, calls


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A small re-ID deployment with drops off."""
    cfg = ScenarioConfig(num_cameras=200, duration_s=12.0, seed=0, tl="wbfs",
                         batching="dynamic", m_max=25, embed_dim=16)
    return _trace(cfg, str(tmp_path_factory.mktemp("trace")), 12)


@pytest.fixture(scope="module")
def traced_drops(tmp_path_factory):
    """A small overloaded deployment with drops on (one VA, one CR, TL-BFS
    at 7 m/s): VA drops at dp2, batches auto-submit, accepts come back."""
    cfg = ScenarioConfig(num_cameras=200, duration_s=20.0, seed=0, tl="bfs",
                         tl_peak_speed=7.0, batching="dynamic", m_max=25,
                         drops_enabled=True, avoid_drop_positives=True, num_va=1,
                         num_cr=1, embed_dim=16)
    return _trace(cfg, str(tmp_path_factory.mktemp("trace_drops")), 20)


def test_every_span_name_is_in_the_closed_list(traced):
    spans, _ = traced
    names = {n for _, _, n in spans}
    assert names, "no repro.* span in the trace"
    assert all(n in SPANS or n.startswith(MODULE_SPAN) for n in names), names
    assert all(n.startswith("repro.") for n in SPANS)
    assert {MODULE_SPAN + m for m in ("VA", "CR", "UV")} <= names


def test_drop_plane_spans_fire_with_drops_on(traced_drops):
    names = {n for _, _, n in traced_drops[0]}
    assert set(DROP_PLANE) <= names and set(DROP_PLANE) <= set(SPANS)
    assert all(n in SPANS or n.startswith(MODULE_SPAN) for n in names), names


def test_drop_plane_spans_stay_silent_with_drops_off(traced):
    assert not set(DROP_PLANE) & {n for _, _, n in traced[0]}


def test_each_step_is_one_des_run_span(traced):
    spans, _ = traced
    assert sum(n == "repro.des.run" for _, _, n in spans) == 11
    ticks = [s for s in spans if s[2] == "repro.tl.tick"]
    assert ticks and all(_parent(t, spans, lambda n: n == "repro.des.run") for t in ticks)


def test_dispatch_sits_in_module_logic_in_a_des_step(traced):
    """A dispatch queues its call inside VA's logic; the step's reads come
    at its end, still inside its ``repro.des.run``, where the first one
    flushes the queue: a launch and its answer's read per query block."""
    spans, calls = traced
    disp = [s for s in spans if s[2] == "repro.reid.dispatch"]
    assert calls > 0 and len(disp) == calls
    in_step = lambda s: _parent(s, spans, lambda n: n == "repro.des.run") is not None
    for d in disp:
        module = _parent(d, spans, lambda n: n.startswith(MODULE_SPAN))
        assert module is not None and module[2] == MODULE_SPAN + "VA"
        assert in_step(module)
        parts = [s[2] for s in spans if s[2].startswith("repro.reid.")
                 and s is not d and _inside(s, d)]
        assert parts == ["repro.reid.prep"]
    flushes = [s for s in spans if s[2] == "repro.reid.flush"]
    steps = sum(n == "repro.des.run" for _, _, n in spans)
    assert 0 < len(flushes) <= min(calls, steps)
    launches = 0
    for f in flushes:
        wait = _parent(f, spans, lambda n: n == "repro.va.reid_wait")
        assert wait is not None and in_step(wait)
        parts = sorted((s[0], s[2]) for s in spans if s[2].startswith("repro.reid.")
                       and s is not f and _inside(s, f))
        names = [n for _, n in parts]
        assert names and names == ["repro.reid.call", "repro.reid.slice"] * (len(names) // 2)
        launches += len(names) // 2
    assert launches == len([s for s in spans if s[2] == "repro.reid.call"])
    builds = [s for s in spans if s[2] == "repro.va.reid_build"]
    waits = [s for s in spans if s[2] == "repro.va.reid_wait"]
    assert len(builds) == len(waits) == calls
    assert all(_parent(s, spans, lambda n: n == MODULE_SPAN + "VA") for s in builds)
    assert all(in_step(s) and not _parent(s, spans, lambda n: n.startswith(MODULE_SPAN))
               for s in waits)


def test_span_leaves_jax_unimported():
    code = ("import sys\n"
            "from repro.core.clock import span\n"
            "with span('repro.des.run'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'span imported jax'\n"
            "print('NO_JAX_OK')\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": SRC}, timeout=120)
    assert "NO_JAX_OK" in res.stdout, res.stdout + res.stderr


def test_observability_plane_names_the_same_spans():
    import repro.obs as obs
    from repro.core import clock

    assert obs.span is clock.span and obs.SPANS is SPANS and obs.MODULE_SPAN == MODULE_SPAN
    text = obs.prometheus_exposition(obs.collect_dispatch(obs.MetricsRegistry()))
    assert "repro_kernel_dispatch_seconds_total" not in text
