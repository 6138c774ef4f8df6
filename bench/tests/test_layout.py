"""Self-tests of the data-driven layout: every name in ``BENCHMARK.json``
finds its file, the query generator gives every seed the same work in
another order, and the metric readers read what they should and nothing
where there is nothing."""

import os
import re

import pytest

from bench import kernels, run, workload
from bench.tests import SIZES

BENCH = workload.load_json(os.path.join(workload.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_file():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = workload.load_json(os.path.join(workload.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = workload.load_cell(w["name"])
        assert cell.traffic["name"] == w["traffic"]
    for m in BENCH["per_layer"]:
        assert callable(run.load_metric(m["name"]))
        for w in m["workloads"]:
            assert w in {x["name"] for x in BENCH["workloads"]}


def test_every_configuration_finds_its_reference():
    for c in BENCH["configs"]:
        ref = run.load_reference(workload.load_json(os.path.join(workload.ROOT, c["file"])))
        assert os.path.dirname(os.path.abspath(ref.__file__)) == workload.BENCH_DIR
        assert callable(ref.books) and callable(ref.refuses)


def test_every_cell_has_its_cpu_size():
    for w in BENCH["workloads"]:
        size = workload.load_json(os.path.join(SIZES, w["name"] + ".json"))
        cell = workload.load_cell(w["name"])
        assert size and set(size) <= set(cell.config["scenario"])
        assert workload.scenario_config(cell, **size)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in run.cell_metrics(BENCH, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(BENCH, w["name"], "per_layer")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_seed_draws_the_queries_not_the_work(cell):
    c = workload.load_cell(cell)
    a = workload.query_plans(c, 2**33 + 5)
    b = workload.query_plans(c, 17)
    assert a == workload.query_plans(c, 2**33 + 5)
    assert a != b
    work = lambda plans: sorted((p["submit_at"], p["tl_peak_speed"]) for p in plans)  # noqa: E731
    assert work(a) == work(b)
    strangers = lambda plans: sum(p["embedding_seed"] is not None for p in plans)  # noqa: E731
    assert strangers(a) == strangers(b) == len(a) // c.traffic["stranger_every"]
    assert [s.submit_at for s in workload.query_specs(a)] == [p["submit_at"] for p in a]


def test_poisson_quantile_arrivals_fall_between_ticks():
    t = workload.load_json(os.path.join(workload.BENCH_DIR, "traffic", "staggered.json"))
    times = workload.arrival_times(t, 1.0)
    assert times[0] == 0.0 and times == sorted(times)
    assert all(x % 1.0 == 0.5 for x in times[1:])
    gaps = [b - a for a, b in zip(times, times[1:])]
    assert sum(gaps) / len(gaps) == pytest.approx(t["arrivals"]["mean_gap_s"], rel=0.15)


def test_peaks_are_keyed_by_device_kind():
    assert kernels.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        kernels.peaks("TPU v9 imaginary")


def _record(**kw):
    rec = {"trace": {"window_s": 2.0, "busy_s": 0.5,
                     "module_s": {"jit_reid_multi_padded": 1e-3}},
           "replays": [{"ticks": 100, "lit": 1500, "engine_used": "interpreted"}],
           "engine": "interpreted",
           "counters": {"reid_multi_calls": 250},
           "compiles_in_window": 0,
           "reid_shapes": [(1, 16, 128)] * 250,
           "peaks": kernels.peaks("TPU v5 lite")}
    rec.update(kw)
    return rec


def test_metric_readers():
    read = lambda name, **kw: run.load_metric(name)(_record(**kw))  # noqa: E731
    assert read("device_idle_pct") == pytest.approx(75.0)
    assert read("reid_dispatches_per_tick") == pytest.approx(2.5)
    assert read("lit_cameras_per_tick") == pytest.approx(15.0)
    assert read("compiles_in_window") == 0.0
    flops, nbytes = kernels.reid_match_multi_work(1, 16, 128)
    want = 100.0 * 250 * max(flops / 197e12, nbytes / 819e9) / 1e-3
    assert read("reid_match_multi_roofline") == pytest.approx(want)
    assert 0 < want < 100


def test_metric_readers_find_nothing_to_read():
    read = lambda name, **kw: run.load_metric(name)(_record(**kw))  # noqa: E731
    assert read("device_idle_pct", trace=None) is None
    assert read("reid_match_multi_roofline", reid_shapes=[]) is None
    assert read("reid_match_multi_roofline",
                trace={"window_s": 1, "busy_s": 0, "module_s": {}}) is None
    assert read("reid_dispatches_per_tick", counters={}) is None
    assert read("engine_device_pct") is None
    engine = [{"ticks": 1, "lit": 1, "engine_used": u}
              for u in ("megastep-host", "megastep-device")]
    assert read("engine_device_pct", engine="megastep", replays=engine) == 50.0

