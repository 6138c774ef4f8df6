"""The drops-on deployment against its plain reference (``bench/dropsim.py``).

The platform runs ``paper1000-drops.overload`` at its CPU size, one replay
through the benchmark's own entry (``bench.run.replay``) with every re-ID
dispatch recorded, and its books are compared with the reference's as the
benchmark compares them: exactly, causes first, and the latencies within
``latency_gap_s``.  At this size VA drops at dp2, batches hold several
frames and probes run; a frame dropped where the reference keeps it, a batch
formed one frame short, and a re-ID match that leaves its frame droppable
are each caught, and so is the reference in float32 time.
"""

import contextlib

import pytest

from bench import check, run, workload
from bench.tests import cpu_size

CELL = "paper1000-drops.overload"
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cell():
    return workload.load_cell(CELL)


def _patched(obj, name, make):
    """A fault: ``obj.name`` replaced by ``make(real)`` while it is open."""

    @contextlib.contextmanager
    def fault():
        real = getattr(obj, name)
        setattr(obj, name, make(real))
        try:
            yield
        finally:
            setattr(obj, name, real)

    return fault


def compare(cell, fault=None, ticks=None):
    """One replay (cut after ``ticks`` frame periods where given) under
    ``fault``: the first exact difference from the reference's books, the
    latency gap, and the re-ID comparison."""
    from repro.kernels import dispatch

    size = cpu_size(CELL)
    cfg = workload.scenario_config(cell, **size)
    plans = workload.query_plans(cell, SEED)
    tap = run.ReidTap(dispatch.reid_match_multi, contextlib.nullcontext)
    tap.recording, tap.replay = True, 0
    steps = []

    def stop():
        steps.append(1)
        return ticks is not None and len(steps) >= ticks

    real = dispatch.reid_match_multi
    dispatch.reid_match_multi = tap
    try:
        with (fault() if fault else contextlib.nullcontext()):
            res, scn, t = run.replay(cell, cfg, workload.query_specs(plans),
                                     contextlib.nullcontext, stop)
    finally:
        dispatch.reid_match_multi = real
    tap.to_host()
    cut = t if res is None else cfg.duration_s + 3.0 * cfg.gamma
    want = run.reference_books(cell, size, plans, [cut])[cut]
    got = check.observe_platform(scn, res, tap.calls)
    diff, gap = check.compare_books(got, want)
    return diff, gap, check.reid_compare(tap.calls), want


def test_whole_replay_matches_the_reference(cell):
    diff, gap, reid, want = compare(cell)
    assert diff is None, check.describe_diff(diff)
    assert gap <= check.LIMITS["latency_gap_s"]
    assert reid["reid_score_gap"] < check.LIMITS["reid_score_gap"]
    assert reid["reid_flag_mismatches"] == 0 and reid["reid_pairs_checked"] > 0
    # The size works the drop plane: dp2 drops, batches of several frames,
    # and probes re-entering VA beside the frames FC sent.
    fc, va = want["exact"]["modules"]["FC"], want["exact"]["modules"]["VA"]
    assert va["dp2"] > 0 and va["batches"] < va["executed"]
    assert va["arrived"] > fc["executed"] - fc["dp3"]


def test_replay_cut_mid_run_matches_the_reference(cell):
    diff, gap, reid, want = compare(cell, ticks=47)
    assert diff is None, check.describe_diff(diff)
    assert gap <= check.LIMITS["latency_gap_s"]
    assert not reid["bad_replays"]
    assert want["exact"]["timeline"][-1][0] == 47.0


def test_frame_dropped_at_dp2_where_the_reference_keeps_it_is_caught(cell):
    from repro.core.pipeline import Task

    def make(real):
        dropped = []

        def drop_first(self, batch, now_local):
            """VA's first droppable frame is dropped at dp2 regardless of its
            budget."""
            ev = batch[0].event
            if self.module != "VA" or dropped or ev.header.avoid_drop:
                return real(self, batch, now_local)
            dropped.append(ev)
            self.stats.dropped_dp2 += 1
            self._on_drop(ev, epsilon=0.0, point=2)
            return real(self, batch[1:], now_local) if len(batch) > 1 else []

        return drop_first

    diff, _, _, _ = compare(cell, _patched(Task, "_drop_before_exec", make))
    assert diff is not None and diff[0] == "/modules/VA/dp2", check.describe_diff(diff)


def test_batch_formed_one_frame_short_is_caught(cell):
    from repro.core.pipeline import Task

    half = cpu_size(CELL)["duration_s"] / 2

    def make(real):
        split = []

        def enqueue(self, batch):
            """VA's first batch of several frames after mid-run is submitted
            without its last frame, which follows as a batch of its own."""
            if self.module != "VA" or split or len(batch) < 2 or self.sim.time < half:
                return real(self, batch)
            split.append(len(batch))
            real(self, batch[:-1])
            real(self, batch[-1:])

        return enqueue

    diff, _, _, _ = compare(cell, _patched(Task, "_enqueue_batch", make))
    assert diff is not None and diff[0] == "/modules/VA/batches", check.describe_diff(diff)


def test_reid_match_that_leaves_its_frame_droppable_is_caught(cell):
    from repro.core import compile as compile_mod

    def make(real):
        def adapt_va(va, avoid, hook=None):
            matched = []

            def reid(events, state):
                """The real re-ID hook; the frames whose match set
                ``avoid_drop`` are noted."""
                before = [ev.header.avoid_drop for ev in events]
                for ev in events:
                    ev.header.avoid_drop = False
                hook(events, state)
                matched[:] = [ev for ev in events if ev.header.avoid_drop]
                for ev, flag in zip(events, before):
                    ev.header.avoid_drop = ev.header.avoid_drop or flag

            inner = real(va, avoid, reid if hook else None)

            def logic(events, state):
                out = inner(events, state)
                for ev in matched:  # the match does not keep its flag
                    ev.header.avoid_drop = False
                return out

            return logic

        return adapt_va

    diff, gap, _, _ = compare(cell, _patched(compile_mod, "_adapt_va", make))
    assert diff is not None or gap > check.LIMITS["latency_gap_s"]


def test_float32_time_control_is_not_correct(cell):
    size = cpu_size(CELL)
    plans = workload.query_plans(cell, SEED)
    horizon = size["duration_s"] + 3.0 * cell.config["scenario"]["gamma"]
    want = run.reference_books(cell, size, plans, [horizon])[horizon]
    got = run.reference_books(cell, size, plans, [horizon], time32=True)[horizon]
    assert check.timed_gap(got["timed"], want["timed"]) > 3 * check.LIMITS["latency_gap_s"]
