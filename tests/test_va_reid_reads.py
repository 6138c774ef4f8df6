"""VA's re-ID reads: with drops off a multi-query run reads its answers at
the end of each DES step, so the dispatch plane answers a step's calls with
one launch per query block; with ``avoid_drop_positives`` each answer is
read at once.  Either way the books are those of a run whose every call is
read at once, and nothing is left queued when ``run_until`` returns.

The deployments are the benchmark's re-ID cells at their CPU sizes
(``bench/tests/sizes``), stepped one frame period at a time as the
benchmark steps them.
"""

import contextlib

import numpy as np
import pytest

from bench import check, workload
from bench.tests import cpu_size
from repro.kernels import dispatch
from repro.query import MultiQueryScenario, QuerySpec
from repro.sim import ScenarioConfig

SEED = 2**31 + 29
CELLS = ("paper1000-reid.steady", "paper1000-reid.staggered")


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


def _step(name, probe_at=()):
    """One replay of cell ``name`` stepped a frame period at a time: its
    books, its snapshots at the simulated times ``probe_at`` (each taken
    inside a step, with the count of calls left queued then), the
    queue's length after each step, and the calls and launches it made."""
    cell = workload.load_cell(name)
    cfg = workload.scenario_config(cell, **cpu_size(name))
    specs = workload.query_specs(workload.query_plans(cell, SEED))
    scn = MultiQueryScenario(cfg, specs)
    probes = []
    for t in probe_at:
        scn.sim.schedule_at(t, lambda: probes.append((dispatch.reid_queued(),
                                                      scn.snapshot())))
    dispatch.reid_flush()
    s0 = dispatch.stats()
    queued = []
    period = 1.0 / cfg.fps
    k = 1
    while k * period <= cfg.duration_s:
        scn.run_until(k * period)
        queued.append(dispatch.reid_queued())
        k += 1
    res = scn.run()
    queued.append(dispatch.reid_queued())
    s1 = dispatch.stats()
    return {"books": check.observe_platform(scn, res, []),
            "snapshot": scn.snapshot(),
            "probes": probes,
            "queued": queued,
            "calls": s1["reid_multi_calls"] - s0["reid_multi_calls"],
            "launches": s1["reid_multi_launches"] - s0["reid_multi_launches"]}


@pytest.fixture(scope="module", params=CELLS)
def runs(request):
    name = request.param
    period = 1.0 / workload.scenario_config(workload.load_cell(name)).fps
    probe_at = [(k + 0.5) * period for k in range(3, 40, 4)]
    late = _step(name, probe_at)
    with _read_at_once():
        eager = _step(name, probe_at)
    return late, eager


def test_books_equal_those_of_reads_at_once(runs):
    late, eager = runs
    assert late["calls"] == eager["calls"] > 0
    assert late["books"] == eager["books"]
    assert late["snapshot"] == eager["snapshot"]
    assert late["books"]["exact"]["global"]["reid_matched"] > 0
    # One launch a call when read at once; the late reads share launches.
    assert eager["launches"] == eager["calls"]
    assert late["launches"] < late["calls"] / 2


def test_nothing_is_left_queued_when_a_step_returns(runs):
    late, eager = runs
    assert late["queued"] and not any(late["queued"]) and not any(eager["queued"])


def test_snapshot_mid_step_equals_the_eager_one(runs):
    late, eager = runs
    assert len(late["probes"]) == len(eager["probes"]) == 10
    # Some probe found calls queued, and the snapshot had them answered.
    assert any(n for n, _ in late["probes"])
    assert not any(n for n, _ in eager["probes"])
    assert [s for _, s in late["probes"]] == [s for _, s in eager["probes"]]


def test_avoid_drop_positives_reads_each_answer_at_once():
    cfg = ScenarioConfig(num_cameras=200, duration_s=20.0, seed=0, tl="bfs",
                         tl_peak_speed=7.0, batching="dynamic", m_max=25,
                         drops_enabled=True, avoid_drop_positives=True, num_va=1,
                         num_cr=1, embed_dim=16)
    scn = MultiQueryScenario(cfg, [QuerySpec(), QuerySpec(embedding_seed=99)])
    dispatch.reid_flush()
    s0 = dispatch.stats()
    for k in range(1, 21):
        scn.run_until(float(k))
        assert not scn._reid_pending and dispatch.reid_queued() == 0
    s1 = dispatch.stats()
    calls = s1["reid_multi_calls"] - s0["reid_multi_calls"]
    assert calls > 0 and s1["reid_multi_launches"] - s0["reid_multi_launches"] == calls
