"""Mega-step engine gate: bit-exactness against the interpreted pipeline.

``ScenarioConfig.engine = "megastep"`` may lower the per-tick hot loop to
the fused device scan (``repro.kernels.megastep``), the host chain mirror,
or the plan-driven tick driver (drops on) — but it is only allowed to exist
because the result is **bit-identical** to the interpreted
``MultiQueryScenario``.  These tests are that gate: every backend is
compared field-by-field (global + per-query summaries, raw latency lists,
active timelines, batch sizes, drop books, requested/applied control
mirrors) against an interpreted run of the same config, and the engine
actually used is asserted so a silent fallback can't masquerade as
coverage.
"""

import copy
import os

import numpy as np
import pytest

# Full-duration golden replays are the strict gate but dominate the tier-1
# wall (see PERF.md §PR-9); they run under REPRO_RUN_SLOW=1 while a
# shortened-horizon equivalent of each stays in tier-1.
slow = pytest.mark.skipif(
    os.environ.get("REPRO_RUN_SLOW", "") != "1",
    reason="full-duration golden replay; set REPRO_RUN_SLOW=1",
)

from repro.query import MultiQueryScenario, QuerySpec
from repro.sim import ScenarioConfig

# 60 cameras / 10 lanes keeps every drops-off case on one compiled chunk
# shape (Cb=64, Nb=8, T->Kb=128) so the module compiles the scan once.
BASE = dict(
    num_cameras=60, duration_s=120.0, seed=0, tl="bfs",
    batching="dynamic", m_max=25,
)

MIXED_SPECS = [
    QuerySpec(tl="wbfs"),
    QuerySpec(tl="bfs", tl_peak_speed=6.0),
    QuerySpec(tl="base"),
    QuerySpec(tl="wbfs", last_seen_camera=40),
]


def _deep(res):
    """Everything observable about a MultiQueryResult, exactly."""
    out = {
        "global": res.result.summary(),
        "g_lat": res.result.latencies,
        "g_active": res.result.active_timeline,
        "g_batch": res.result.batch_sizes,
        "g_drops": res.result.drops_by_task,
        "states": res.states,
        "per": {},
    }
    for qid, r in res.per_query.items():
        st = res.registry.get(qid)
        out["per"][qid] = {
            "summary": res.per_query_summary(qid),
            "lat": r.latencies,
            "active": r.active_timeline,
            "sourced": st.sourced,
            "requested": sorted(st.requested),
            "applied": sorted(st.applied),
        }
    return out


def _run(cfg, specs, engine, **mq_kw):
    c = copy.deepcopy(cfg)
    c.engine = engine
    scn = MultiQueryScenario(c, copy.deepcopy(specs), **mq_kw)
    res = scn.run()
    return _deep(res), scn.engine_used, scn.engine_fallback_reason


def check_bit_identical(cfg, specs, expect_engine, expect_reason="", **mq_kw):
    ref, ref_engine, _ = _run(cfg, specs, "interpreted", **mq_kw)
    assert ref_engine == "interpreted"
    got, engine, reason = _run(cfg, specs, "megastep", **mq_kw)
    assert (engine, reason) == (expect_engine, expect_reason)
    assert got == ref
    return got


# --------------------------------------------------------------------- #
# Device backend (drops off, finite-parameter table TLs)                  #
# --------------------------------------------------------------------- #
def test_device_mixed_tls_bit_identical():
    """base + bfs + wbfs (default and custom seeds/speeds) in one run."""
    check_bit_identical(ScenarioConfig(**BASE), MIXED_SPECS, "megastep-device")


def test_device_static_batch_one():
    cfg = ScenarioConfig(**{**BASE, "batching": "static", "static_batch": 1})
    specs = [QuerySpec(tl="bfs"), QuerySpec(tl="wbfs", tl_peak_speed=3.0)]
    check_bit_identical(cfg, specs, "megastep-device")


def test_device_single_query():
    check_bit_identical(
        ScenarioConfig(**BASE), [QuerySpec(tl="wbfs")], "megastep-device"
    )


def test_device_multi_lane():
    cfg = ScenarioConfig(**{**BASE, "num_va": 4, "num_cr": 4})
    specs = [QuerySpec(tl="bfs"), QuerySpec(tl="wbfs")]
    check_bit_identical(cfg, specs, "megastep-device")


@pytest.mark.parametrize("f64_exact", [True, False], ids=["device", "host"])
def test_sink_ties_follow_scheduler_order(monkeypatch, f64_exact):
    """Two lanes with long CR queues: events of different source ticks
    reach the sink at the same instant.  The scheduler orders them by when
    each arrival was scheduled (queue depth, then the arrival that armed
    the queue), not by source tick; the latency lists must keep its order
    on both backends."""
    import jax

    from repro.kernels.megastep import ops

    monkeypatch.setitem(ops._X64_EXACT, jax.default_backend(), f64_exact)
    cfg = ScenarioConfig(**{**BASE, "seed": 2, "num_va": 2, "num_cr": 2})
    specs = [QuerySpec(tl="wbfs", tl_peak_speed=3.0 + i % 3) for i in range(4)]
    if f64_exact:
        check_bit_identical(cfg, specs, "megastep-device")
    else:
        check_bit_identical(cfg, specs, "megastep-host", "x64-emulated")


# --------------------------------------------------------------------- #
# Host backend (object TLs / overload divergence)                         #
# --------------------------------------------------------------------- #
def test_host_fallback_on_overload():
    """A TLBase query holding all 300 cameras active at fps=1 overloads the
    10-lane pipeline: in-flight detections grow past the device ring cap,
    the scan flags divergence, and the run lands on the host mirror —
    still bit-identical."""
    cfg = ScenarioConfig(**{**BASE, "num_cameras": 300, "duration_s": 150.0})
    specs = [
        QuerySpec(tl="wbfs"),
        QuerySpec(tl="bfs", tl_peak_speed=6.0),
        QuerySpec(tl="base"),
        QuerySpec(tl="wbfs", last_seen_camera=120),
    ]
    check_bit_identical(cfg, specs, "megastep-host", "device-capacity")


def test_host_probabilistic_tl():
    """TLProbabilistic has no finite (radius, hop) table — the host backend
    drives the real TL objects through the chain mirror."""
    cfg = ScenarioConfig(**{**BASE, "num_cameras": 150, "duration_s": 60.0,
                            "tl": "prob"})
    specs = [QuerySpec(tl="prob"), QuerySpec(tl="wbfs")]
    check_bit_identical(cfg, specs, "megastep-host")


def test_host_kernel_spotlight_mode():
    """Shortened-horizon tier-1 version of the full-duration golden below."""
    cfg = ScenarioConfig(**{**BASE, "tl": "wbfs", "duration_s": 25.0})
    specs = [QuerySpec(tl="wbfs"), QuerySpec(tl="wbfs", tl_peak_speed=3.0)]
    check_bit_identical(cfg, specs, "megastep-host", spotlight_mode="kernel")


@pytest.mark.slow
@slow
def test_host_kernel_spotlight_mode_full_duration():
    cfg = ScenarioConfig(**{**BASE, "tl": "wbfs"})
    specs = [QuerySpec(tl="wbfs"), QuerySpec(tl="wbfs", tl_peak_speed=3.0)]
    check_bit_identical(cfg, specs, "megastep-host", spotlight_mode="kernel")


# --------------------------------------------------------------------- #
# Device failures reach the host mirror loudly                            #
# --------------------------------------------------------------------- #
SHORT = {**BASE, "duration_s": 40.0}


def test_device_error_falls_back_with_reason(monkeypatch):
    """A device scan that raises hands the run to the host mirror, which
    stays bit-identical, and the run says why."""
    from repro.kernels.megastep import ops
    from repro.obs import probe_backend

    def broken_chunk(*args, **kwargs):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(ops, "_CHUNK_FN", broken_chunk)
    # Restored at teardown: later runs in this process see a clean plane.
    monkeypatch.setattr(ops, "_LAST_FALLBACK", ops._LAST_FALLBACK)
    check_bit_identical(
        ScenarioConfig(**SHORT), [QuerySpec(tl="wbfs")], "megastep-host",
        "device-error: RuntimeError('injected device failure')",
    )
    name, ok, detail = probe_backend()
    assert name == "backend" and not ok and "injected" in detail


def test_cpu_f64_is_ieee_binary64():
    from repro.kernels.megastep import ops

    assert ops.x64_exact()


def test_emulated_f64_refuses_device_with_reason(monkeypatch):
    """Where f64 is not IEEE binary64 (a TPU lowers it to f32 pairs) the
    bit-identity contract cannot hold on the device: the run goes to the
    host mirror and records ``x64-emulated``."""
    import jax

    from repro.kernels.megastep import ops

    monkeypatch.setitem(ops._X64_EXACT, jax.default_backend(), False)
    check_bit_identical(
        ScenarioConfig(**SHORT), [QuerySpec(tl="wbfs"), QuerySpec(tl="bfs")],
        "megastep-host", "x64-emulated",
    )


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """``chip_smoke.py`` exits non-zero and prints no result line on the
    CPU backend, and when it stands alone without the repo."""
    import shutil
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    shutil.copy(os.path.join(root, "chip_smoke.py"), tmp_path)
    stderr = {}
    for where, cwd in (("repo", root), ("alone", tmp_path)):
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode != 0, proc.stdout
        assert '"ok"' not in proc.stdout
        stderr[where] = proc.stderr
    assert "needs a TPU, JAX found cpu" in stderr["repo"]
    assert "No module named 'repro'" in stderr["alone"]


# --------------------------------------------------------------------- #
# Drops on: plan-driven tick driver over the real event DAG               #
# --------------------------------------------------------------------- #
def test_des_drops_streaming():
    cfg = ScenarioConfig(**{**BASE, "drops_enabled": True})
    specs = [QuerySpec(tl="bfs"), QuerySpec(tl="wbfs")]
    check_bit_identical(cfg, specs, "megastep-des")


def test_des_drops_static_batch():
    cfg = ScenarioConfig(**{**BASE, "drops_enabled": True,
                            "avoid_drop_positives": True,
                            "batching": "static", "static_batch": 10,
                            "duration_s": 90.0})
    specs = [QuerySpec(tl="wbfs"), QuerySpec(tl="base")]
    check_bit_identical(cfg, specs, "megastep-des")


# --------------------------------------------------------------------- #
# Interpreted fallbacks: everything else keeps the reference pipeline     #
# --------------------------------------------------------------------- #
def test_interpreted_fallback_reasons():
    from repro.sim import DynamismSpec

    small = {**BASE, "duration_s": 20.0}

    cfg = ScenarioConfig(**small, dynamism=DynamismSpec())
    _, engine, reason = _run(cfg, [QuerySpec(tl="wbfs")], "megastep")
    assert (engine, reason) == ("interpreted", "dynamism")

    _, engine, reason = _run(
        ScenarioConfig(**small),
        [QuerySpec(tl="wbfs"), QuerySpec(tl="wbfs", submit_at=5.0)],
        "megastep",
    )
    assert (engine, reason) == ("interpreted", "query-lifecycle")


def test_interpreted_fallback_is_bit_identical():
    """The fallback isn't a degraded mode: engine="megastep" on an
    ineligible config must return exactly the interpreted result."""
    from repro.sim import DynamismSpec

    cfg = ScenarioConfig(**{**BASE, "duration_s": 40.0},
                         dynamism=DynamismSpec())
    specs = [QuerySpec(tl="wbfs")]
    ref, _, _ = _run(cfg, specs, "interpreted")
    got, engine, _ = _run(cfg, specs, "megastep")
    assert engine == "interpreted"
    assert got == ref
