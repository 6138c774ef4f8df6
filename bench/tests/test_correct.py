"""``correct`` must hold on a sound run and fail on the controls and on
each fault the cells can have, at a size a CPU test run holds.

The harness runs as in a benchmark run, except that it does not look for a
chip and the deployment is cut to a few hundred cameras and seconds.
"""

import contextlib
import os

import numpy as np
import pytest

from bench import check, refsim, run, workload

CELLS = [w["name"] for w in workload.load_json(
    os.path.join(workload.ROOT, "BENCHMARK.json"))["workloads"]]
SMALL = {"paper1000-reid.steady": dict(num_cameras=120, duration_s=40.0),
         "paper1000-reid.staggered": dict(num_cameras=300, duration_s=330.0)}
SEED = 2**31 + 11


def small_run(cell, seconds=0.0, whole=True, **kw):
    return run.run_cell(cell, SEED, seconds, False, require_tpu=False, whole=whole,
                        override=SMALL[cell], log=lambda _s: None, **kw)


def _patched(obj, name, value):
    @contextlib.contextmanager
    def fault():
        real = getattr(obj, name)
        setattr(obj, name, value)
        try:
            yield
        finally:
            setattr(obj, name, real)

    return fault


def _dispatch_fault(change):
    """Break the re-ID dispatch where it answers: ``change(gallery,
    queries, mask, threshold, real)`` returns the answer."""
    from repro.kernels import dispatch

    @contextlib.contextmanager
    def fault():
        inner = dispatch.reid_match_multi  # the harness's tap
        tap_fn = inner.fn
        inner.fn = lambda g, q, *, mask=None, threshold=0.5: change(
            g, q, mask, threshold, tap_fn)
        try:
            yield
        finally:
            inner.fn = tap_fn

    return fault


@pytest.mark.parametrize("whole", [True, False], ids=["whole", "cut"])
@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, whole):
    out = small_run(cell, seconds=0.0 if whole else 1.0, whole=whole)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["reid_score_gap"]["value"] < check.LIMITS["reid_score_gap"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    out = small_run(cell, matcher=check.reid_control)
    assert not out["correct"]
    assert out["checks"]["reid_score_gap"]["value"] > 3 * check.LIMITS["reid_score_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_float32_time_control_is_not_correct(cell):
    c = workload.load_cell(cell)
    config = dict(c.config, scenario=dict(c.config["scenario"], **SMALL[cell]))
    world = refsim.World(config["scenario"])
    plans = workload.query_plans(c, SEED)
    horizon = config["scenario"]["duration_s"] + 3.0 * config["scenario"]["gamma"]
    want = refsim.Reference(config, world, plans).run_until(horizon).observe()
    got = refsim.Reference(config, world, plans, time32=True).run_until(horizon).observe()
    assert check.timed_gap(got["timed"], want["timed"]) > 3 * check.LIMITS["latency_gap_s"]


def test_step_that_leaves_its_state_unchanged_is_caught():
    from repro.sim.simulator import DiscreteEventSimulator

    out = small_run("paper1000-reid.steady",
                    fault=_patched(DiscreteEventSimulator, "run",
                                   lambda self, until=None: None))
    assert not out["correct"]
    assert out["checks"]["replays_differing"]["value"] >= 1


def test_spotlight_altered_where_it_is_produced_is_caught():
    from repro.core.tracking import TLWBFS

    real = TLWBFS.spotlight
    out = small_run("paper1000-reid.staggered",
                    fault=_patched(TLWBFS, "spotlight",
                                   lambda self, now: set(list(real(self, now))[1:])))
    assert not out["correct"]
    assert out["checks"]["replays_differing"]["value"] >= 1


def test_half_of_the_batch_left_out_is_caught():
    def half(g, q, mask, thr, real):
        n = len(g) // 2  # a batch of one row loses its row
        scores = np.full((len(g), len(q)), -np.inf, np.float32)
        flags = np.zeros((len(g), len(q)), bool)
        if n:
            s, m = real(g[:n], q, mask=mask[:n], threshold=thr)
            scores[:n], flags[:n] = np.asarray(s), np.asarray(m)
        return scores, flags

    out = small_run("paper1000-reid.staggered", fault=_dispatch_fault(half))
    assert not out["correct"]


def test_answer_altered_where_it_is_produced_is_caught():
    def flip(g, q, mask, thr, real):
        s, m = real(g, q, mask=mask, threshold=thr)
        m = np.array(m)
        m[0] = ~m[0] & mask[0]
        return s, m

    out = small_run("paper1000-reid.steady", fault=_dispatch_fault(flip))
    assert not out["correct"]
    assert out["checks"]["reid_flag_mismatches"]["value"] >= 1


def test_engine_entry_matches_the_reference():
    cell = workload.load_cell("paper1000-reid.steady")
    scenario = dict(cell.config["scenario"], embed_dim=0, **SMALL[cell.name])
    cell.config = dict(cell.config, engine="megastep", scenario=scenario)
    cfg = workload.scenario_config(cell)
    plans = workload.query_plans(cell, 5)
    res, scn, _ = run.replay(cell, cfg, workload.query_specs(plans), contextlib.nullcontext)
    assert scn.engine_used.startswith("megastep")
    horizon = cfg.duration_s + 3.0 * cfg.gamma
    want = run.reference_books(cell, {}, plans, [horizon])[horizon]
    diff, gap = check.compare_books(check.observe_platform(scn, res, []), want)
    assert diff is None and gap <= check.LIMITS["latency_gap_s"]
