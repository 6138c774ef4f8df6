"""SweepRunner: concurrent grid execution must be bit-identical to
sequential runs, share one world build per key, and fall back to serial."""

import os

import pytest

from repro.sim import ScenarioConfig, SweepRunner, TrackingScenario


def _grid():
    base = dict(num_cameras=1000, duration_s=40.0, seed=0, tl="bfs")
    return [
        ("sb1", ScenarioConfig(**base, batching="static", static_batch=1,
                               tl_peak_speed=4.0)),
        ("db25", ScenarioConfig(**base, batching="dynamic", m_max=25,
                                tl_peak_speed=6.0)),
        ("nob", ScenarioConfig(**base, batching="nob", m_max=25,
                               tl_peak_speed=4.0)),
        ("drops", ScenarioConfig(**base, batching="dynamic", m_max=25,
                                 tl_peak_speed=7.0, num_va=5, num_cr=5,
                                 drops_enabled=True, avoid_drop_positives=True)),
        # Unpicklable config member: the fork path must carry it through the
        # inherited grid, not pickle it.
        ("bwdrop", ScenarioConfig(**base, batching="dynamic", m_max=25,
                                  tl_peak_speed=4.0,
                                  bandwidth_schedule=lambda t: 1.0 if t < 20.0 else 0.03)),
    ]


@pytest.fixture(scope="module")
def sequential_summaries():
    return {name: TrackingScenario(cfg).run().summary() for name, cfg in _grid()}


def test_serial_sweep_bit_identical_to_sequential(sequential_summaries):
    res = SweepRunner(mode="serial").run(_grid())
    assert res.mode == "serial"
    assert [r.name for r in res.records] == [name for name, _ in _grid()]
    for rec in res.records:
        assert rec.summary == sequential_summaries[rec.name], rec.name


@pytest.mark.skipif(not SweepRunner.fork_available(), reason="needs fork")
def test_fork_sweep_bit_identical_to_sequential():
    """Runs in a fresh interpreter: the pytest process has JAX (multithreaded
    XLA) initialized by other test modules, and forking a JAX-initialized
    parent is the documented deadlock hazard the runner itself avoids."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        from repro.sim import SweepRunner, TrackingScenario
        from tests.test_sweep import _grid

        seq = {name: TrackingScenario(cfg).run().summary() for name, cfg in _grid()}
        res = SweepRunner(mode="fork").run(_grid())
        assert res.mode == "fork" and res.workers >= 1
        assert [r.name for r in res.records] == [name for name, _ in _grid()]
        for rec in res.records:
            assert rec.summary == seq[rec.name], rec.name
            assert rec.run_s > 0.0 and rec.build_s > 0.0
        print("FORK_SWEEP_OK")
        """
    )
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=root,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "FORK_SWEEP_OK" in proc.stdout


def test_sweep_builds_each_world_once(sequential_summaries):
    res = SweepRunner(mode="serial").run(_grid())
    # All five configs share one (num_cameras, seed, horizon) world; it may
    # already be resident from an earlier sweep, but never built twice.
    assert res.worlds_built <= 1
    assert sum(r.world_build_s for r in res.records) == 0.0


def test_cold_serial_rebuilds_per_case(sequential_summaries):
    grid = _grid()[:2]
    res = SweepRunner(mode="serial", share_worlds=False).run(grid)
    assert res.mode == "serial"
    assert res.worlds_built == len(grid)  # one world built per case
    assert res.world_build_s > 0.0
    for rec in res.records:
        assert rec.world_build_s > 0.0  # every case built its own world
        assert rec.summary == sequential_summaries[rec.name]


def test_cold_auto_forces_serial_and_fork_cold_rejected():
    runner = SweepRunner(mode="auto", share_worlds=False)
    res = runner.run(_grid()[:2])
    assert res.mode == "serial"
    with pytest.raises(ValueError):
        SweepRunner(mode="fork", share_worlds=False)


def test_auto_mode_resolution():
    runner = SweepRunner(mode="auto")
    mode, workers = runner._resolve_mode(1)
    assert (mode, workers) == ("serial", 1)
    if SweepRunner.fork_available() and (os.cpu_count() or 1) > 1:
        mode, workers = runner._resolve_mode(4)
        assert mode == "fork" and 2 <= workers <= 4


def test_invalid_mode_rejected():
    with pytest.raises(ValueError):
        SweepRunner(mode="threads")


def test_device_dispatching_cases_are_jax_touching():
    """Every case that dispatches to a device counts, so auto mode keeps
    it out of a fork pool: a chip takes one process."""
    from repro.sim.sweep import QueryCase, _touches_jax

    base = dict(num_cameras=100, duration_s=10.0, seed=0, tl="bfs")
    assert not _touches_jax(ScenarioConfig(**base))
    assert _touches_jax(ScenarioConfig(**base, embed_dim=16))
    assert _touches_jax(ScenarioConfig(**base, engine="megastep"))
    assert _touches_jax(QueryCase(2, ScenarioConfig(**base), spotlight_mode="kernel"))
    assert not _touches_jax(QueryCase(2, ScenarioConfig(**base)))
    mode, workers = SweepRunner(mode="auto")._resolve_mode(4, needs_jax=True)
    assert (mode, workers) == ("serial", 1)


@pytest.mark.skipif(not SweepRunner.fork_available(), reason="needs fork")
def test_forced_fork_of_device_grid_refused_once_jax_is_up():
    import jax

    jax.devices()  # bring the backend up in this process
    grid = [("mega", ScenarioConfig(num_cameras=100, duration_s=10.0, seed=0,
                                    tl="bfs", engine="megastep"))]
    with pytest.raises(RuntimeError, match="mode='serial'"):
        SweepRunner(mode="fork").run(grid)
