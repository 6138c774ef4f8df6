"""Bucket-batched kernel dispatch: padding must be invisible in results,
operands must stay device-resident, and an entire sweep of varying batch
sizes must compile each kernel at most once per bucket shape."""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.roadnet import make_road_network
from repro.kernels import dispatch
from repro.kernels.reid_match.ref import reid_match_ref
from repro.kernels.spotlight_ball.ops import spotlight_ball as ops_spotlight_ball


@pytest.fixture(scope="module")
def road():
    return make_road_network(num_vertices=180, target_edges=500, seed=17)


def test_bucket_rounding():
    assert dispatch.bucket(1) == dispatch.BUCKET_MIN
    assert dispatch.bucket(8) == 8
    assert dispatch.bucket(9) == 16
    assert dispatch.bucket(16) == 16
    assert dispatch.bucket(17) == 32
    assert dispatch.bucket(3, minimum=1) == 4
    with pytest.raises(ValueError):
        dispatch.bucket(0)


def test_spotlight_ball_padding_is_invisible(road):
    indptr, indices, weights = road.csr()
    rng = np.random.default_rng(2)
    for Q in (1, 3, 8, 9, 13):
        sources = rng.integers(0, road.num_vertices, Q).astype(np.int32)
        radii = rng.uniform(50.0, 2500.0, Q).astype(np.float32)
        got = np.asarray(dispatch.spotlight_ball(indptr, indices, weights, sources, radii))
        want = np.asarray(
            ops_spotlight_ball(indptr, indices, weights.astype(np.float32), sources, radii)
        )
        assert got.shape == (Q, road.num_vertices)
        np.testing.assert_array_equal(got, want)


def test_reid_match_padding_matches_up_to_ulp():
    # Padding the gallery changes the GEMM blocking, so scores may differ
    # from the unpadded call in the last ulp (deterministically per shape);
    # matches must agree everywhere the score isn't within an ulp of the
    # threshold.
    rng = np.random.default_rng(3)
    threshold = 0.3
    for D in (16, 32):
        queries = rng.normal(size=(3, D)).astype(np.float32)
        for N in (1, 2, 8, 11, 40):
            gallery = rng.normal(size=(N, D)).astype(np.float32)
            got_s, got_b, got_m = [
                np.asarray(x) for x in dispatch.reid_match(gallery, queries, threshold=threshold)
            ]
            ref_s, ref_b, ref_m = [
                np.asarray(x) for x in reid_match_ref(gallery, queries, threshold=threshold)
            ]
            assert got_s.shape == ref_s.shape
            np.testing.assert_allclose(got_s, ref_s, rtol=2e-6, atol=2e-7)
            clear = np.abs(ref_s - threshold) > 1e-5
            np.testing.assert_array_equal(got_m[clear], ref_m[clear])
            # Self-consistency: is_match is exactly scores >= threshold.
            np.testing.assert_array_equal(
                got_m, got_s >= np.float32(threshold)
            )


def test_reid_negative_scores_not_clobbered_by_padding():
    # All-negative similarities: a zero pad query would win the max if the
    # mask were missing.
    gallery = np.array(
        [[1, 1, 0, 0], [1, 2, 0, 0], [2, 1, 0, 0]], dtype=np.float32
    )
    queries = -np.eye(4, dtype=np.float32)[:2]
    scores, _, matched = [np.asarray(x) for x in dispatch.reid_match(gallery, queries)]
    ref_scores, _, ref_matched = [
        np.asarray(x) for x in reid_match_ref(gallery, queries)
    ]
    np.testing.assert_allclose(scores, ref_scores, rtol=2e-6, atol=2e-7)
    assert (scores < 0).all() and not matched.any()
    np.testing.assert_array_equal(matched, ref_matched)


def test_dense_adjacency_cached_per_network(road):
    indptr, indices, weights = road.csr()
    src = np.zeros(2, np.int32)
    rad = np.full(2, 100.0, np.float32)
    dispatch.spotlight_ball(indptr, indices, weights, src, rad)
    before = dispatch.stats()
    dispatch.spotlight_ball(indptr, indices, weights, src, rad)
    after = dispatch.stats()
    assert after["device_cache_hits"] > before["device_cache_hits"]
    assert after["device_cache_misses"] == before["device_cache_misses"]


def test_at_most_one_compile_per_bucket_shape():
    """Acceptance: across a whole sweep of varying batch sizes, the padded
    kernels recompile at most once per bucket shape (jit cache-miss count
    == distinct bucket shapes dispatched).  Uses a private network so cache
    state from other tests cannot mask compilations."""
    net = make_road_network(num_vertices=150, target_edges=420, seed=23)
    indptr, indices, weights = net.csr()
    rng = np.random.default_rng(4)

    # Warm both kernels once so module-level compilation state exists.
    # D=24 is private to this test: other tests must not pre-compile the
    # reid shapes whose cache misses are being counted.
    D = 24
    dispatch.spotlight_ball(indptr, indices, weights,
                            np.zeros(1, np.int32), np.full(1, 10.0, np.float32))
    dispatch.reid_match(rng.normal(size=(2, D)).astype(np.float32),
                        rng.normal(size=(1, D)).astype(np.float32))
    base = dispatch.jit_cache_sizes()

    # A "sweep" of calls: many batch sizes, only two buckets each (8, 16).
    for Q in (1, 2, 3, 5, 8, 9, 12, 16, 7, 11):
        sources = rng.integers(0, net.num_vertices, Q).astype(np.int32)
        radii = rng.uniform(10.0, 500.0, Q).astype(np.float32)
        dispatch.spotlight_ball(indptr, indices, weights, sources, radii)
    for N in (1, 4, 8, 9, 16, 3, 13):
        dispatch.reid_match(rng.normal(size=(N, D)).astype(np.float32),
                            rng.normal(size=(1, D)).astype(np.float32))

    sizes = dispatch.jit_cache_sizes()
    # Q in 1..8 -> bucket 8 (already warm), 9..16 -> bucket 16: exactly one
    # new compile per kernel despite 10 (7) distinct batch sizes.
    assert sizes["ball"] - base["ball"] == 1
    assert sizes["reid"] - base["reid"] == 1

    # Re-running the same sweep adds no compiles at all.
    for Q in (2, 9, 16, 5):
        sources = rng.integers(0, net.num_vertices, Q).astype(np.int32)
        radii = rng.uniform(10.0, 500.0, Q).astype(np.float32)
        dispatch.spotlight_ball(indptr, indices, weights, sources, radii)
    assert dispatch.jit_cache_sizes()["ball"] == sizes["ball"]


def test_spotlight_multi_kernel_path_uses_dispatch(road):
    from repro.core.tracking import TLProbabilistic

    cams = {c: c for c in range(road.num_vertices)}
    tl = TLProbabilistic(road, cams, entity_speed=4.0, coverage=0.9)
    for i, cam in enumerate((3, 40, 99)):
        tl.track(f"e{i}", cam, float(i))
    before = dispatch.stats()["ball_calls"]
    py = tl.spotlight_multi(25.0)
    kr = tl.spotlight_multi(25.0, use_kernel=True)
    assert py == kr and py
    assert dispatch.stats()["ball_calls"] == before + 1


def test_scenario_reid_path_counts_matches():
    """embed_dim > 0 routes VA batches through the bucketed re-id matcher;
    entity frames embed near the entity embedding, so matches track the
    generated positives."""
    from repro.sim import ScenarioConfig, TrackingScenario

    cfg = ScenarioConfig(
        num_cameras=60, road_vertices=150, duration_s=30.0, seed=61,
        embed_dim=16, tl="base", batching="static", static_batch=10,
    )
    res = TrackingScenario(cfg).run()
    assert res.positives_generated > 0
    assert res.reid_matched > 0
    # The matcher sees every frame exactly once; true matches cannot exceed
    # total frames and should be in the neighbourhood of the positives.
    assert res.reid_matched <= res.source_events
    # Disabled path records nothing.
    cfg0 = ScenarioConfig(
        num_cameras=60, road_vertices=150, duration_s=30.0, seed=61,
        tl="base", batching="static", static_batch=10,
    )
    assert TrackingScenario(cfg0).run().reid_matched == 0


def test_reid_multi_buckets_and_compile_accounting():
    """The query-major kernel obeys the same dispatch contracts as the
    single-query one: power-of-two bucket padding on BOTH axes, call/shape
    stats, and at most one jit compile per bucket shape."""
    rng = np.random.default_rng(9)
    D = 40  # private to this test, like the single-query compile test
    before = dispatch.stats()["reid_multi_calls"]
    np.asarray(dispatch.reid_match_multi(rng.normal(size=(2, D)).astype(np.float32),
                                         rng.normal(size=(1, D)).astype(np.float32))[0])
    base = dispatch.jit_cache_sizes()["reid_multi"]
    # Gallery 1..64 and queries 1..8 share one (64, 8, D) bucket shape: the
    # row axis never launches below REID_MULTI_ROWS.
    assert dispatch.REID_MULTI_ROWS == 64
    for N, Q in ((1, 1), (3, 2), (8, 8), (5, 7)):
        g = rng.normal(size=(N, D)).astype(np.float32)
        q = rng.normal(size=(Q, D)).astype(np.float32)
        scores, matched = dispatch.reid_match_multi(g, q)
        assert np.asarray(scores).shape == (N, Q)
        assert np.asarray(matched).shape == (N, Q)
    assert dispatch.jit_cache_sizes()["reid_multi"] == base
    # A new bucket (Q > 8) costs exactly one more compile.
    np.asarray(dispatch.reid_match_multi(rng.normal(size=(2, D)).astype(np.float32),
                                         rng.normal(size=(9, D)).astype(np.float32))[0])
    assert dispatch.jit_cache_sizes()["reid_multi"] == base + 1
    assert dispatch.stats()["reid_multi_calls"] == before + 6


def test_reid_multi_one_program_per_call_and_host_answers():
    """Once a bucket is warm, a call with any other (N, Q) pair in it
    compiles nothing: the matcher is the one program a launch runs, and
    each call read on its own is one launch.  The answers are handles that
    read to host arrays bit-equal to the padded kernel's output cut there,
    the flags its float32 comparison with the threshold."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(14)
    D, thr = 44, 0.05  # D private to this test: its bucket starts cold
    pairs = ((1, 9), (2, 16), (7, 9), (8, 13), (5, 12))  # one (64, 16) bucket
    calls = []
    for N, Q in pairs:
        calls.append((rng.normal(size=(N, D)).astype(np.float32),
                      rng.normal(size=(Q, D)).astype(np.float32),
                      rng.random((N, Q)) < 0.7))
    np.asarray(dispatch.reid_match_multi(*calls[0][:2], mask=calls[0][2],
                                         threshold=thr)[1])

    compiles = []

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    launches = dispatch.stats()["reid_multi_launches"]
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        answers = []
        for g, q, m in calls[1:]:
            scores, matched = dispatch.reid_match_multi(g, q, mask=m, threshold=thr)
            answers.append((np.asarray(scores), np.asarray(matched)))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert dispatch.stats()["reid_multi_launches"] == launches + len(calls) - 1

    for (g, q, m), (scores, matched) in zip(calls[1:], answers):
        N, Q = m.shape
        assert type(scores) is np.ndarray and type(matched) is np.ndarray
        assert scores.dtype == np.float32 and matched.dtype == np.bool_
        assert scores.shape == matched.shape == (N, Q)
        g_pad = np.zeros((64, D), np.float32)
        g_pad[:N] = g
        q_pad = np.zeros((16, D), np.float32)
        q_pad[:Q] = q
        m_pad = np.zeros((64, 16), bool)
        m_pad[:N, :Q] = m
        want_s = np.asarray(dispatch._REID_MULTI_PADDED(
            jnp.asarray(g_pad), jnp.asarray(q_pad), jnp.asarray(m_pad)))[:N, :Q]
        np.testing.assert_array_equal(scores, want_s)
        np.testing.assert_array_equal(matched, m & (want_s >= np.float32(thr)))
    flags = np.concatenate([m.ravel() for _, m in answers])
    assert flags.any() and not flags.all()


def test_jit_cache_is_bounded(monkeypatch):
    """Sweeping more distinct bucket shapes than MAX_JIT_SHAPES must not
    grow a kernel's compile cache without bound: the LRU drops the cache on
    overflow and rebuilds it for the working set."""
    monkeypatch.setattr(dispatch, "MAX_JIT_SHAPES", 4)
    rng = np.random.default_rng(6)
    # Each feature width D is its own bucket shape for the reid kernel.
    for D in (52, 56, 60, 64, 68, 72, 76):
        dispatch.reid_match(rng.normal(size=(2, D)).astype(np.float32),
                            rng.normal(size=(1, D)).astype(np.float32))
        assert dispatch.jit_cache_sizes()["reid"] <= 4
        assert len(dispatch._JIT_LRU["reid"]) <= 4
    # A shape inside the live working set does not recompile.
    size = dispatch.jit_cache_sizes()["reid"]
    dispatch.reid_match(rng.normal(size=(2, 76)).astype(np.float32),
                        rng.normal(size=(1, 76)).astype(np.float32))
    assert dispatch.jit_cache_sizes()["reid"] == size


# --------------------------------------------------------------------- #
# The re-ID queue: calls answered by one launch per query block          #
# --------------------------------------------------------------------- #
def _reid_calls(rng, block, sizes, D):
    """One ``(gallery, mask)`` call against ``block`` per row count."""
    return [(rng.normal(size=(n, D)).astype(np.float32),
             rng.random((n, block.shape[0])) < 0.8) for n in sizes]


def _launches():
    return dispatch.stats()["reid_multi_launches"]


def test_reid_queue_one_launch_for_a_block_and_answers_as_one_at_a_time():
    rng = np.random.default_rng(31)
    D, thr = 48, 0.1
    block = rng.normal(size=(5, D)).astype(np.float32)
    calls = _reid_calls(rng, block, (1, 3, 1, 7, 2), D)
    alone = []
    for g, m in calls:
        s, f = dispatch.reid_match_multi(g, block, mask=m, threshold=thr)
        alone.append((np.asarray(s), np.asarray(f)))
    before = _launches()
    queued = [dispatch.reid_match_multi(g, block, mask=m, threshold=thr) for g, m in calls]
    assert dispatch.reid_queued() == len(calls) and _launches() == before
    np.asarray(queued[2][1])
    assert dispatch.reid_queued() == 0 and _launches() == before + 1
    for (s, f), (want_s, want_f) in zip(queued, alone):
        np.testing.assert_array_equal(np.asarray(s), want_s)
        np.testing.assert_array_equal(np.asarray(f), want_f)
    assert _launches() == before + 1


def test_reid_queue_launches_once_per_query_block():
    rng = np.random.default_rng(32)
    D = 48
    a = rng.normal(size=(3, D)).astype(np.float32)
    b = rng.normal(size=(9, D)).astype(np.float32)
    dispatch.reid_flush()
    before = _launches()
    answers = [dispatch.reid_match_multi(g, blk, mask=m)
               for blk in (a, b, a, b)
               for g, m in _reid_calls(rng, blk, (2,), D)]
    assert dispatch.reid_queued() == 4
    np.asarray(answers[-1][0])
    assert _launches() == before + 2 and dispatch.reid_queued() == 0
    assert [np.asarray(s).shape for s, _ in answers] == [(2, 3), (2, 9), (2, 3), (2, 9)]


def test_reid_queue_row_bound_forces_a_flush():
    rng = np.random.default_rng(33)
    D = 48
    block = rng.normal(size=(4, D)).astype(np.float32)
    rows = dispatch.REID_MULTI_ROWS
    (g1, m1), (g2, m2), (g3, m3) = _reid_calls(rng, block, (rows - 10, 11, rows + 5), D)
    dispatch.reid_flush()
    before = _launches()
    first = dispatch.reid_match_multi(g1, block, mask=m1)
    assert dispatch.reid_queued() == 1 and _launches() == before
    # 11 more rows would overflow the block's launch: the queue goes first.
    second = dispatch.reid_match_multi(g2, block, mask=m2)
    assert dispatch.reid_queued() == 1 and _launches() == before + 1
    # A call of more rows than the bound flushes the queue and launches alone.
    third = dispatch.reid_match_multi(g3, block, mask=m3)
    assert dispatch.reid_queued() == 1 and _launches() == before + 2
    assert np.asarray(third[0]).shape == (rows + 5, 4)
    assert _launches() == before + 3
    assert np.asarray(first[0]).shape == (rows - 10, 4)
    assert np.asarray(second[0]).shape == (11, 4)
    assert _launches() == before + 3


def test_reid_answer_handles_offer_no_async_copy():
    rng = np.random.default_rng(34)
    g = rng.normal(size=(2, 48)).astype(np.float32)
    q = rng.normal(size=(3, 48)).astype(np.float32)
    for answer in dispatch.reid_match_multi(g, q):
        assert isinstance(answer, dispatch.ReidAnswer)
        assert not hasattr(answer, "copy_to_host_async")
    dispatch.reid_flush()


def test_reid_answers_read_to_read_only_host_arrays():
    rng = np.random.default_rng(35)
    g = rng.normal(size=(6, 48)).astype(np.float32)
    q = rng.normal(size=(3, 48)).astype(np.float32)
    scores, matched = dispatch.reid_match_multi(g, q, threshold=0.0)
    for answer, dtype in ((scores, np.float32), (matched, np.bool_)):
        host = np.asarray(answer)
        assert type(host) is np.ndarray and host.dtype == dtype and host.shape == (6, 3)
        assert not host.flags.writeable
        with pytest.raises(ValueError):
            host[0, 0] = host[0, 1]
        copy = np.array(answer)
        assert copy.flags.writeable and np.array_equal(copy, host)
        assert np.asarray(answer, dtype=np.float64).dtype == np.float64


def test_reid_flags_never_leave_their_mask():
    rng = np.random.default_rng(36)
    D = 48
    block = rng.normal(size=(7, D)).astype(np.float32)
    # Gallery rows close to the queries, so most evaluated pairs match.
    answers = []
    for _ in range(6):
        g = (block[rng.integers(0, 7, 5)] + 0.05 * rng.normal(size=(5, D))).astype(np.float32)
        m = rng.random((5, 7)) < 0.5
        answers.append((m, dispatch.reid_match_multi(g, block, mask=m, threshold=-0.5)))
    for m, (scores, matched) in answers:
        s, f = np.asarray(scores), np.asarray(matched)
        assert f[m].any() and not f[~m].any()
        assert np.all(np.isneginf(s[~m])) and np.all(np.isfinite(s[m]))
