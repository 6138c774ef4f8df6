"""Self-tests of the span reduction (``bench/spans.py``) and its readers:
synthetic nested spans, readers with nothing to read, and one traced CPU
run of the harness at a small size.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

import os

import pytest

from bench import run, spans
from bench.tests import cpu_size

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
NEW = ("reid_dispatch_us", "reid_launch_us", "reid_wait_us", "tl_tick_us",
       "module_self_pct", "des_self_pct", "des_step_p95_ms")
US = 1e-6

# Times in us (scaled to ns below): the harness's bench.des around a DES
# step, module logic in it, the re-ID tap (bench.reid) around the dispatch,
# and a second DES step outside any harness span.
SPANS = [(5, 70, "bench.des"), (10, 60, "repro.des.run"), (20, 50, "repro.module.VA"),
         (22, 48, "bench.reid"), (24, 46, "repro.reid.dispatch"),
         (26, 30, "repro.reid.put"), (30, 40, "repro.reid.call"),
         (40, 44, "repro.reid.slice"), (72, 90, "repro.des.run"),
         (95, 120, "repro.des.drain")]


@pytest.fixture
def reduced():
    ns = [(s * 1e3, e * 1e3, n) for s, e, n in SPANS]
    return spans.reduce_spans((0.0, 100e3), ns, [[(32e3, 34e3)]])


def test_self_time_goes_to_the_innermost_span(reduced):
    got = {n: s / US for n, s in reduced["self_s"].items()}
    want = {"bench.des": 15, "repro.des.run": 38, "repro.module.VA": 4,
            "bench.reid": 4, "repro.reid.dispatch": 4, "repro.reid.put": 4,
            "repro.reid.call": 10, "repro.reid.slice": 4, "repro.des.drain": 5}
    assert got == pytest.approx(want)
    assert reduced["window_s"] == pytest.approx(100 * US)
    # The drain is clipped to the window.
    assert reduced["durations"]["repro.des.drain"] == pytest.approx([5 * US])
    assert reduced["count"]["repro.des.run"] == 2


def test_device_idle_goes_to_the_innermost_span(reduced):
    idle = {n: s / US for n, s in reduced["idle_s"].items()}
    assert idle["repro.reid.call"] == pytest.approx(8)  # busy 32-34
    assert idle["other"] == pytest.approx(12)  # 0-5, 70-72 and 90-95
    assert sum(idle.values()) == pytest.approx(98)


def test_cover_is_the_program_share_of_the_harness_spans(reduced):
    # bench.des + bench.reid hold 5-70; program spans cover 10-60 of it.
    assert reduced["cover"] == pytest.approx(50 / 65)


def _read(name, reduced, monkeypatch):
    monkeypatch.setattr(spans, "load", lambda record=None, trace_dir=None: reduced)
    return run.load_metric(name)({"trace": {}})


def test_readers(reduced, monkeypatch):
    read = lambda name: _read(name, reduced, monkeypatch)  # noqa: E731
    assert read("reid_dispatch_us") == pytest.approx(22)
    assert read("reid_launch_us") == pytest.approx(18)
    assert read("module_self_pct") == pytest.approx(4)
    assert read("des_self_pct") == pytest.approx(43)
    # p95 of the two DES steps, 50 and 18 us, by linear interpolation.
    assert read("des_step_p95_ms") == pytest.approx((18 + 0.95 * 32) * 1e-3)
    assert read("reid_wait_us") is None and read("tl_tick_us") is None


def test_readers_find_nothing_to_read(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    for name in NEW:
        assert run.load_metric(name)({"trace": {"window_s": 1.0}}) is None
        assert run.load_metric(name)({"trace": None}) is None
    if os.path.exists(DATA):  # a trace with bench.* spans and no program span
        assert spans.parse(DATA) is None


def test_the_trace_is_where_the_harness_writes_it():
    assert spans.TRACE_DIR == run.TRACE_DIR


def test_traced_cpu_run_reports_every_span_metric():
    out = run.run_cell("paper1000-reid.steady", 2**31 + 11, 1.0, True, require_tpu=False,
                       override=cpu_size("paper1000-reid.steady"),
                       log=lambda _s: None)
    assert out["correct"], out["checks"]
    for name in NEW:
        assert out["metrics"][name]["value"] > 0, name
    reduced = spans.load()
    assert reduced["count"]["repro.reid.dispatch"] == reduced["count"]["bench.reid"]
    assert reduced["cover"] > 0.95
