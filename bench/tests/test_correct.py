"""``correct`` must hold on a sound run and fail on the controls and on
each fault the cells can have, at a size a CPU test run holds.

The harness runs as in a benchmark run, except that it does not look for a
chip and the deployment is cut to a few hundred cameras and seconds.
"""

import contextlib
import math
import os

import numpy as np
import pytest

from bench import check, run, workload
from bench.tests import cpu_size

CELLS = [w["name"] for w in workload.load_json(
    os.path.join(workload.ROOT, "BENCHMARK.json"))["workloads"]]
REFSIM_CELLS = [c for c in CELLS if "reference" not in workload.load_cell(c).config]
SEED = 2**31 + 11


def small_run(cell, seconds=0.0, whole=True, log=lambda _s: None, **kw):
    return run.run_cell(cell, SEED, seconds, False, require_tpu=False, whole=whole,
                        override=cpu_size(cell), log=log, **kw)


def first_difference(cell, fault):
    """The run's verdict and the first difference it logged."""
    lines = []
    out = small_run(cell, fault=fault, log=lines.append)
    said = [s for s in lines if s.startswith("reference: compared")]
    return out, said[-1].split("first difference ", 1)[1]


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
    size = cpu_size(cell)
    scn = run.cell_config(c, size)["scenario"]
    plans = workload.query_plans(c, SEED)
    horizon = scn["duration_s"] + 3.0 * scn["gamma"]
    want = run.reference_books(c, size, plans, [horizon])[horizon]
    got = run.reference_books(c, size, plans, [horizon], time32=True)[horizon]
    assert check.timed_gap(got["timed"], want["timed"]) > 3 * check.LIMITS["latency_gap_s"]


@pytest.mark.parametrize("cell", REFSIM_CELLS)
def test_refsim_named_gives_the_books_of_no_name(cell):
    c = workload.load_cell(cell)
    plans = workload.query_plans(c, SEED)
    cuts = [3.0, 10.0]
    want = run.reference_books(c, cpu_size(cell), plans, cuts)
    assert want[10.0]["exact"]["modules"]["VA"]["arrived"] > 0
    c.config = dict(c.config, reference="refsim")
    assert run.reference_books(c, cpu_size(cell), plans, cuts) == want


def test_unknown_reference_fails_and_names_its_file():
    c = workload.load_cell(CELLS[0])
    c.config = dict(c.config, reference="no_such_reference")
    with pytest.raises(FileNotFoundError, match="bench/no_such_reference.py"):
        run.reference_books(c, cpu_size(CELLS[0]), workload.query_plans(c, SEED), [1.0])


@pytest.mark.parametrize("cell", REFSIM_CELLS)
def test_drops_on_is_refused_before_the_window(cell, monkeypatch, capsys):
    def no_warm_up(*_a, **_kw):
        raise AssertionError("warmed up a cell its reference refuses")

    monkeypatch.setattr(run, "warm_up", no_warm_up)
    with pytest.raises(run.Refused, match="refsim refuses drops_enabled: true"):
        run.run_cell(cell, SEED, 1.0, False, require_tpu=False,
                     override=dict(cpu_size(cell), drops_enabled=True), log=lambda _s: None)

    c = workload.load_cell(cell)
    c.config = dict(c.config, scenario=dict(c.config["scenario"], drops_enabled=True))
    monkeypatch.setattr(workload, "load_cell", lambda _name: c)
    assert run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "1"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "drops_enabled: true" in err


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


def test_frame_dropped_at_dp2_is_caught():
    from repro.core.pipeline import Task

    real = Task.on_arrival
    dropped = []

    def on_arrival(self, ev):
        if self.module != "VA" or dropped:
            return real(self, ev)
        dropped.append(self.name)
        self.stats.arrived += 1
        self.stats.dropped_dp2 += 1
        self._on_drop(ev, epsilon=0.0, point=2)

    out, first = first_difference("paper1000-reid.steady",
                                  _patched(Task, "on_arrival", on_arrival))
    assert dropped and not out["correct"]
    assert out["checks"]["replays_differing"]["value"] >= 1
    assert first.startswith("/modules/VA/dp2: 1 != 0"), first


def test_batch_of_two_where_the_reference_serves_one_is_caught():
    from repro.core.pipeline import PendingEvent, Task

    real = Task.on_arrival
    held = []

    def on_arrival(self, ev):
        """VA holds its first frame and runs it in one batch with the next
        frame the same instance receives."""
        if self.module != "VA" or len(held) == 2 or (held and held[0][0] is not self):
            return real(self, ev)
        self.stats.arrived += 1
        held.append((self, PendingEvent(event=ev, arrival=self.sim.time, deadline=math.inf)))
        if len(held) == 2:
            self._enqueue_batch([pe for _, pe in held])

    out, first = first_difference("paper1000-reid.steady",
                                  _patched(Task, "on_arrival", on_arrival))
    assert len(held) == 2 and not out["correct"]
    assert out["checks"]["replays_differing"]["value"] >= 1
    assert first.startswith("/modules/VA/batches: "), first


def test_engine_entry_matches_the_reference():
    cell = workload.load_cell("paper1000-reid.steady")
    scenario = dict(cell.config["scenario"], embed_dim=0, **cpu_size(cell.name))
    cell.config = dict(cell.config, engine="megastep", scenario=scenario)
    cfg = workload.scenario_config(cell)
    plans = workload.query_plans(cell, 5)
    res, scn, _ = run.replay(cell, cfg, workload.query_specs(plans), contextlib.nullcontext)
    assert scn.engine_used.startswith("megastep")
    horizon = cfg.duration_s + 3.0 * cfg.gamma
    want = run.reference_books(cell, {}, plans, [horizon])[horizon]
    diff, gap = check.compare_books(check.observe_platform(scn, res, []), want)
    assert diff is None and gap <= check.LIMITS["latency_gap_s"]
