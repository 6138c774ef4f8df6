"""Serving engine + Anveshak-scheduled stages."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.models import init_params, reduced_config
from repro.serving import (
    Generator,
    ServedStage,
    StageRequest,
    bucket_for,
    calibrate_xi,
    embed_frames,
    init_reid_tower,
    match,
    sample_tokens,
)


def test_bucket_for():
    assert bucket_for(1) == 1
    assert bucket_for(3) == 4
    assert bucket_for(100) == 128
    assert bucket_for(10_000) == 128  # clamped to largest


def test_sampling_greedy_and_masked():
    logits = jnp.asarray([[0.0, 5.0, 1.0, 9.0]])
    rng = jax.random.PRNGKey(0)
    assert int(sample_tokens(logits, rng, greedy=True)[0]) == 3
    # padded-vocab mask: index 3 is out of the real vocab
    assert int(sample_tokens(logits, rng, greedy=True, vocab_size=3)[0]) == 1


def test_generator_decodes_consistently_with_forward():
    from repro.models import forward

    cfg = reduced_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = Generator(cfg, params)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    out = gen.generate(prompts, max_new_tokens=4)
    assert out.shape == (2, 4)
    # first generated token == argmax of the forward logits at the prompt end
    logits, _ = forward(params, cfg, {"tokens": prompts})
    expect = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(expect))


class TestServedStage:
    def make_stage(self, gamma=5.0, drops=True):
        tower = init_reid_tower(jax.random.PRNGKey(1), d_in=64, d_embed=64)
        step = lambda x: embed_frames(tower, jnp.asarray(x))
        xi = calibrate_xi(step, (64,), buckets=(1, 4, 16), repeats=1)
        return ServedStage(
            "CR", step, xi, gamma=gamma, m_max=16, buckets=(1, 4, 16), drops_enabled=drops
        )

    def test_processes_requests(self):
        stage = self.make_stage()
        results = []
        for _ in range(8):
            r = stage.submit(StageRequest(np.random.randn(64).astype(np.float32),
                                          source_time=stage.clock()))
            if r:
                results.extend(r)
        r = stage.flush()
        if r:
            results.extend(r)
        done = [x for x in results if not x.dropped]
        assert len(done) >= 1
        assert all(x.output is not None and x.output.shape == (64,) for x in done)

    def test_drops_stale_requests(self):
        stage = self.make_stage(gamma=0.5)
        # Teach the budget a small value via a reject-style path: directly
        # install a budget so DP1 has something to compare against.
        stage.budget.set_budget(0.01)
        stale = StageRequest(
            np.zeros(64, np.float32), source_time=stage.clock() - 10.0
        )
        res = stage.submit(stale)
        assert res is not None and res[0].dropped

    def test_avoid_drop_protects(self):
        stage = self.make_stage(gamma=0.5)
        stage.budget.set_budget(0.01)
        protected = StageRequest(
            np.zeros(64, np.float32), source_time=stage.clock() - 10.0, avoid_drop=True
        )
        res = stage.submit(protected)
        done = (res or []) + (stage.flush() or [])
        assert all(not r.dropped for r in done)

    def test_telemetry_snapshot_mirrors_dynamism_trace_fields(self):
        """ServedStage exposes the same telemetry row the discrete-event
        plane's DynamismTrace samples (budget, queue, the three drop-point
        counters, signal counters) — one tracing surface for both planes."""
        from repro.sim.dynamism import TRACE_FIELDS

        stage = self.make_stage()
        t0 = stage.telemetry()
        assert set(t0) == set(TRACE_FIELDS)
        assert t0["dp1"] == t0["dp2"] == t0["dp3"] == 0
        # A DP1 drop shows up in the split AND keeps the "dropped" total.
        stage.budget.set_budget(0.01)
        stage.submit(StageRequest(np.zeros(64, np.float32),
                                  source_time=stage.clock() - 10.0))
        t1 = stage.telemetry()
        assert t1["dp1"] == 1 and stage.stats["dropped"] == 1
        # Signals land in the counters the trace samples.
        stage.on_accept(event_id=123, epsilon=1.0, xi_bar=0.5)
        stage.on_reject(event_id=124, epsilon=1.0, q_bar=0.5)
        t2 = stage.telemetry()
        assert t2["accepts"] == 1 and t2["rejects"] == 1
        assert t2["beta"] == stage.budget.min_budget()

    def test_telemetry_query_id_dimension(self):
        """Multi-query serving: per-query rows share the sim plane's
        TRACE_FIELDS shape, counters split by StageRequest.query_id, and
        drops are charged to the owning query."""
        from repro.sim.dynamism import TRACE_FIELDS

        stage = self.make_stage(drops=False)
        for qid in (7, 7, 9, None):
            res = stage.submit(
                StageRequest(
                    np.zeros(64, np.float32),
                    source_time=stage.clock(),
                    query_id=qid,
                )
            )
        stage.flush()
        assert stage.query_ids() == [7, 9]
        t7, t9 = stage.telemetry(7), stage.telemetry(9)
        assert set(t7) == set(TRACE_FIELDS)
        assert t7["executed"] == 2 and t9["executed"] == 1
        # The stage-wide row still counts everything (incl. untagged).
        assert stage.telemetry()["executed"] == 4
        # A DP1 drop lands in the owning query's row only.
        stage2 = self.make_stage()
        stage2.budget.set_budget(0.01)
        stage2.submit(
            StageRequest(
                np.zeros(64, np.float32),
                source_time=stage2.clock() - 10.0,
                query_id=3,
            )
        )
        assert stage2.telemetry(3)["dp1"] == 1
        assert stage2.telemetry(4)["dp1"] == 0

    def test_publish_metrics_bridges_telemetry_rows(self):
        """The obs bridge re-emits stage-wide and per-query telemetry rows
        as registered WALL-domain metrics: every TRACE_FIELDS column lands
        either in the gauge (beta/queue) or the counter (the rest), values
        match ``telemetry()`` exactly, and nothing serving-side leaks into
        the SIM determinism digest."""
        from repro.obs import SIM, MetricsRegistry
        from repro.sim.dynamism import TRACE_FIELDS

        stage = self.make_stage(drops=False)
        for qid in (7, 7, 9, None):
            stage.submit(
                StageRequest(
                    np.zeros(64, np.float32),
                    source_time=stage.clock(),
                    query_id=qid,
                )
            )
        stage.flush()
        reg = MetricsRegistry()
        stage.publish_metrics(reg)
        row = stage.telemetry()
        sev = reg.get("repro_stage_events_total")
        sgauge = reg.get("repro_stage_row")
        for fld in TRACE_FIELDS:
            if fld in ("beta", "queue"):
                assert sgauge.value(stage="CR", field=fld) == row[fld]
            elif row[fld]:
                assert sev.value(stage="CR", kind=fld) == row[fld]
        q7 = stage.telemetry(query_id=7)
        qev = reg.get("repro_stage_query_events_total")
        assert qev.value(stage="CR", query="7", kind="executed") == q7["executed"]
        assert reg.get("repro_stage_query_row").value(
            stage="CR", query="9", field="beta"
        ) == stage.telemetry(query_id=9)["beta"]
        # Serving metrics are wall-domain: the SIM digest must not see them.
        assert not any(m.domain == SIM for m in reg.collect())
        assert "repro_stage" not in reg.exposition(include_wall=False)

    def test_query_major_bucket_padding(self):
        """set_queries pads the live-query block to a power-of-two bucket
        and the step runs query-major: one device call serves every query,
        and growing within the bucket never changes the padded shape."""
        shapes = []

        def step(x, qblock, nq):
            shapes.append((x.shape, tuple(qblock.shape), int(nq)))
            return jnp.asarray(x)

        stage = ServedStage(
            "VA", step, lambda b: 0.0001 * b, gamma=5.0, m_max=4,
            buckets=(1, 4), drops_enabled=False,
        )
        stage.set_queries(np.ones((3, 16), np.float32))
        stage.submit(StageRequest(np.zeros(16, np.float32),
                                  source_time=stage.clock()))
        stage.flush()
        assert shapes and shapes[-1][1] == (8, 16)  # 3 -> bucket(3) == 8
        assert shapes[-1][2] == 3
        stage.set_queries(np.ones((5, 16), np.float32))
        stage.submit(StageRequest(np.zeros(16, np.float32),
                                  source_time=stage.clock()))
        stage.flush()
        assert shapes[-1][1] == (8, 16) and shapes[-1][2] == 5
        # Empty block falls back to the single-query step signature.
        stage.set_queries(np.zeros((0, 16), np.float32))
        assert stage._query_block is None


def test_reid_match_pipeline():
    tower = init_reid_tower(jax.random.PRNGKey(2), d_in=32, d_embed=16)
    frames = jax.random.normal(jax.random.PRNGKey(3), (20, 32))
    query = embed_frames(tower, frames[5:6])
    scores, matched = match(tower, frames, query, threshold=0.999)
    assert scores.shape == matched.shape == (20, 1)
    assert bool(matched[5, 0])
    assert int(np.argmax(scores[:, 0])) == 5


# --------------------------------------------------------------------- #
# App-compiler lowering: one spec, two planes                            #
# --------------------------------------------------------------------- #
class TestLowerAppStages:
    def _app(self, **specs):
        from repro.core.dataflow import ModuleSpec, TrackingApp, fc_is_active
        from repro.core.roadnet import make_road_network
        from repro.core.tracking import TLBase

        road = make_road_network(num_vertices=30, target_edges=84, seed=0)
        return TrackingApp(
            name="served",
            fc=fc_is_active,
            va=lambda c, f, s: [(c, x) for x in f],
            cr=lambda c, v, s: [(c, x) for x in v],
            tl=TLBase(road, {0: 0}),
            gamma=0.75,
            specs=specs,
        )

    def test_stages_resolve_from_app_and_deployment(self):
        from repro.core.compile import DeploymentSpec, linear_xi
        from repro.core.dataflow import ModuleSpec
        from repro.serving import lower_app_stages

        app = self._app(
            VA=ModuleSpec(m_max=8, xi=linear_xi(0.001, 0.0005)),
            CR=ModuleSpec(m_max=4, xi=linear_xi(0.002, 0.001)),
        )
        stages = lower_app_stages(
            app,
            DeploymentSpec(drops_enabled=True),
            {"VA": lambda x: x, "CR": lambda x: x * 2},
        )
        va, cr = stages["VA"], stages["CR"]
        assert va.name == "served/VA" and cr.name == "served/CR"
        assert va.gamma == cr.gamma == 0.75  # app QoS, both planes
        assert va.batcher.m_max == 8 and cr.batcher.m_max == 4
        assert va.drops_enabled and cr.drops_enabled
        assert cr.upstream is va  # reject/accept chain VA <- CR
        assert va.xi(2) == pytest.approx(0.002)
        # The stages actually serve: submit one request through VA.
        res = va.submit(StageRequest(np.ones(4, np.float32), source_time=va.clock()))
        assert res and not res[0].dropped
        np.testing.assert_allclose(res[0].output, np.ones(4, np.float32))

    def test_non_dynamic_batching_is_rejected(self):
        from repro.core.compile import DeploymentSpec, linear_xi
        from repro.core.dataflow import ModuleSpec
        from repro.serving import lower_stage

        app = self._app(VA=ModuleSpec(batching="static", xi=linear_xi(0.001, 0.0)))
        with pytest.raises(ValueError, match="dynamic"):
            lower_stage("VA", app, DeploymentSpec(), lambda x: x)

    def test_missing_cost_model_calibrates_from_step(self):
        from repro.core.compile import DeploymentSpec, linear_xi
        from repro.core.dataflow import ModuleSpec
        from repro.serving import lower_stage

        app = self._app()  # no xi anywhere
        with pytest.raises(ValueError, match="payload_shape"):
            lower_stage("VA", app, DeploymentSpec(), lambda x: x)
        stage = lower_stage(
            "VA", app, DeploymentSpec(), lambda x: x,
            payload_shape=(4,), buckets=(1, 2),
        )
        assert stage.xi(1) > 0.0  # measured, monotone-ish cost model
        # An *explicit* zero cost model is a declaration, not an absence:
        # it must be honored, never overridden by calibration.
        free = self._app(VA=ModuleSpec(xi=linear_xi(0.0, 0.0)))
        stage = lower_stage("VA", free, DeploymentSpec(), lambda x: x)
        assert stage.xi(8) == 0.0

    def test_cr_drop_rejects_into_va_budget(self):
        """The VA <- CR signal chain is live: a CR-side drop calls the VA
        stage's on_reject with the lateness epsilon."""
        from repro.core.compile import DeploymentSpec, linear_xi
        from repro.core.dataflow import ModuleSpec
        from repro.serving import lower_app_stages

        app = self._app(
            VA=ModuleSpec(xi=linear_xi(0.001, 0.0)),
            CR=ModuleSpec(xi=linear_xi(0.001, 0.0)),
        )
        stages = lower_app_stages(
            app, DeploymentSpec(drops_enabled=True),
            {"VA": lambda x: x, "CR": lambda x: x},
        )
        va, cr = stages["VA"], stages["CR"]
        rejects = []
        va.on_reject = lambda eid, eps, q_bar: rejects.append((eid, eps, q_bar))
        # Teach CR a finite budget, then submit a hopelessly stale request:
        # DP1 drops it and the reject must reach the VA hook.
        cr.budget.set_budget(0.05)
        res = cr.submit(
            StageRequest(np.zeros(4, np.float32), source_time=cr.clock() - 10.0)
        )
        assert res and res[0].dropped
        assert len(rejects) == 1
        eid, eps, _ = rejects[0]
        assert eid == res[0].event_id and eps > 0.0
