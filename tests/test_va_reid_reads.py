"""VA's re-ID reads, one rule for both drivers: with drops off a run reads
its answers at the end of each DES step, so the dispatch plane answers a
step's calls with one launch per query block; with
``avoid_drop_positives`` each answer is read at once.  Either way the books
are those of a run whose every call is read at once, and nothing is left
queued when ``run_until`` returns.

The deployments are the benchmark's re-ID cells at their CPU sizes
(``bench/tests/sizes``), stepped one frame period at a time as the
benchmark steps them, driven by ``MultiQueryScenario`` as the benchmark
drives them and by the single-query ``TrackingScenario``.
"""

import contextlib

import numpy as np
import pytest

from bench import check, workload
from bench.tests import cpu_size
from repro.kernels import dispatch
from repro.query import MultiQueryScenario, QuerySpec
from repro.sim import ScenarioConfig, TrackingScenario

SEED = 2**31 + 29
CASES = (("paper1000-reid.steady", "multi"), ("paper1000-reid.staggered", "multi"),
         ("paper1000-reid.steady", "single"))


@contextlib.contextmanager
def _read_at_once():
    """``dispatch.reid_match_multi`` answering each call as it is made."""
    real = dispatch.reid_match_multi

    def eager(gallery, queries, *, mask=None, threshold=0.5):
        scores, matched = real(gallery, queries, mask=mask, threshold=threshold)
        return np.asarray(scores), np.asarray(matched)

    dispatch.reid_match_multi = eager
    try:
        yield
    finally:
        dispatch.reid_match_multi = real


def _scenario(name, driver):
    """Cell ``name`` at its CPU size, its queries served by one
    ``MultiQueryScenario`` or (``driver="single"``) its entity tracked by a
    ``TrackingScenario``, and what reads the scenario's books mid-run."""
    cell = workload.load_cell(name)
    cfg = workload.scenario_config(cell, **cpu_size(name))
    if driver == "multi":
        scn = MultiQueryScenario(cfg, workload.query_specs(workload.query_plans(cell, SEED)))
        return cfg, scn, scn.snapshot

    scn = TrackingScenario(cfg)

    def snapshot():
        # What a single-query snapshot would be: the step's answers credited.
        scn._settle_reid()
        return {"time": scn.sim.time, "reid_matched": scn._reid_matched}

    return cfg, scn, snapshot


def _books(scn, res):
    if isinstance(scn, MultiQueryScenario):
        return check.observe_platform(scn, res, [])
    return {"exact": {"global": dict(res.summary(), reid_matched=res.reid_matched)}}


def _step(name, driver, probe_at=()):
    """One replay of cell ``name`` stepped a frame period at a time: its
    books, its snapshots at the simulated times ``probe_at`` (each taken
    inside a step, with the count of calls left queued then), the
    queue's length after each step, and the calls and launches it made,
    the most launches in one step among them."""
    cfg, scn, snapshot = _scenario(name, driver)
    probes = []
    for t in probe_at:
        scn.sim.schedule_at(t, lambda: probes.append((dispatch.reid_queued(),
                                                      snapshot())))
    dispatch.reid_flush()
    s0 = dispatch.stats()
    queued = []
    step_launches = []
    period = 1.0 / cfg.fps
    k = 1
    while k * period <= cfg.duration_s:
        before = dispatch.stats()["reid_multi_launches"]
        scn.run_until(k * period)
        step_launches.append(dispatch.stats()["reid_multi_launches"] - before)
        queued.append(dispatch.reid_queued())
        k += 1
    res = scn.run()
    queued.append(dispatch.reid_queued())
    s1 = dispatch.stats()
    return {"books": _books(scn, res),
            "snapshot": snapshot(),
            "probes": probes,
            "queued": queued,
            "step_launches": step_launches,
            "calls": s1["reid_multi_calls"] - s0["reid_multi_calls"],
            "launches": s1["reid_multi_launches"] - s0["reid_multi_launches"]}


@pytest.fixture(scope="module", params=CASES, ids=["-".join(c) for c in CASES])
def runs(request):
    name, driver = request.param
    period = 1.0 / workload.scenario_config(workload.load_cell(name)).fps
    probe_at = [(k + 0.5) * period for k in range(3, 40, 4)]
    late = _step(name, driver, probe_at)
    with _read_at_once():
        eager = _step(name, driver, probe_at)
    return driver, late, eager


def test_books_equal_those_of_reads_at_once(runs):
    driver, late, eager = runs
    assert late["calls"] == eager["calls"] > 0
    assert late["books"] == eager["books"]
    assert late["snapshot"] == eager["snapshot"]
    assert late["books"]["exact"]["global"]["reid_matched"] > 0
    # One launch a call when read at once; the late reads share launches.
    assert eager["launches"] == eager["calls"]
    assert late["launches"] < late["calls"] / 2
    if driver == "single":
        # One query block: a step's calls are answered by one launch.
        assert max(late["step_launches"]) == 1


def test_nothing_is_left_queued_when_a_step_returns(runs):
    _, late, eager = runs
    assert late["queued"] and not any(late["queued"]) and not any(eager["queued"])


def test_snapshot_mid_step_equals_the_eager_one(runs):
    _, late, eager = runs
    assert len(late["probes"]) == len(eager["probes"]) == 10
    # Some probe found calls queued, and the snapshot had them answered.
    assert any(n for n, _ in late["probes"])
    assert not any(n for n, _ in eager["probes"])
    assert [s for _, s in late["probes"]] == [s for _, s in eager["probes"]]


@pytest.mark.parametrize("driver", ["multi", "single"])
def test_avoid_drop_positives_reads_each_answer_at_once(driver):
    cfg = ScenarioConfig(num_cameras=200, duration_s=20.0, seed=0, tl="bfs",
                         tl_peak_speed=7.0, batching="dynamic", m_max=25,
                         drops_enabled=True, avoid_drop_positives=True, num_va=1,
                         num_cr=1, embed_dim=16)
    if driver == "multi":
        scn = MultiQueryScenario(cfg, [QuerySpec(), QuerySpec(embedding_seed=99)])
    else:
        scn = TrackingScenario(cfg)
    dispatch.reid_flush()
    s0 = dispatch.stats()
    for k in range(1, 21):
        scn.run_until(float(k))
        assert not scn._reid_pending and dispatch.reid_queued() == 0
    s1 = dispatch.stats()
    calls = s1["reid_multi_calls"] - s0["reid_multi_calls"]
    assert calls > 0 and s1["reid_multi_launches"] - s0["reid_multi_launches"] == calls
