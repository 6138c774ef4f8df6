"""Self-tests of the trace reduction: synthetic intervals, and the small
TPU trace recorded by ``record_trace.py`` (``data/small.xplane.pb``).

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_idle_goes_to_the_innermost_host_span():
    spans = [(0, 10, "bench.des"), (2, 4, "bench.reid"), (12, 14, "bench.replay_setup")]
    states = trace.host_states(spans)
    assert states == [(0, 2, "bench.des"), (2, 4, "bench.reid"), (4, 10, "bench.des"),
                      (12, 14, "bench.replay_setup")]
    idle = trace.gaps([(3, 4)], 0, 15)  # the device ran only in [3, 4)
    acc = trace.attribute(idle, states)
    assert acc == {"bench.des": 8, "bench.reid": 1, "other": 3, "bench.replay_setup": 2}
    assert sum(acc.values()) == 14


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(DATA):
        pytest.skip("no recorded trace")
    return trace.reduce_trace(DATA)


def test_recorded_trace_window_and_busy(small):
    assert small["devices"] == 1
    assert 0.006 < small["window_s"] < 1.0  # three 2 ms sleeps and three calls
    assert 0 < small["busy_s"] < small["window_s"]


def test_recorded_trace_names_the_reid_program(small):
    assert small["module_calls"]["jit_reid_multi_padded"] == 3
    # A program's span also holds the short gaps between its ops.
    assert small["busy_s"] / 2 < small["module_s"]["jit_reid_multi_padded"] < small["window_s"]


def test_recorded_trace_idle_by_host_state(small):
    idle = small["idle_by_host"]
    assert idle["bench.des"] >= 0.006  # the sleeps, with no device op in them
    total = sum(idle.values())
    assert total == pytest.approx(small["window_s"] - small["busy_s"], rel=1e-6)
    bd = trace.breakdown(small)
    assert bd["device_ops"][0][0] in small["module_s"]
    assert len(bd["idle_gaps"]) <= 10
