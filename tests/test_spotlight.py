"""Spotlight-search equivalence + scale regression (perf PR acceptance).

* The incremental :class:`ResumableDijkstra` must match the from-scratch
  ``weighted_ball`` exactly, across growing radii and restart episodes.
* The batched CSR relaxation (``spotlight_ball`` ref path, run in x64) must
  match the pure-Python Dijkstra ball bit-exactly on 100 random queries.
* The Pallas kernel step (interpret mode) must match the jnp reference
  exactly (min-plus is rounding-free under tiling).
* A 10k-camera scenario must build + run within a wall-clock ceiling.
"""

import math
import time

import numpy as np
import pytest

from repro.core.roadnet import ResumableDijkstra, make_road_network
from repro.core.tracking import Detection, TLProbabilistic, TLWBFS


@pytest.fixture(scope="module")
def road():
    return make_road_network(num_vertices=200, target_edges=560, seed=5)


# --------------------------------------------------------------------- #
# Incremental Dijkstra == from-scratch weighted ball                     #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_resumable_matches_weighted_ball(seed):
    net = make_road_network(num_vertices=150, target_edges=420, seed=seed)
    rng = np.random.default_rng(seed)
    for src in rng.integers(0, 150, size=5):
        search = ResumableDijkstra(net, int(src))
        for radius in np.cumsum(rng.uniform(20.0, 400.0, size=6)):
            incremental = search.ball(float(radius))
            scratch = net.weighted_ball(int(src), float(radius))
            assert incremental == scratch


def test_resumable_settle_order_is_nondecreasing(road):
    search = ResumableDijkstra(road, 0)
    ball = search.ball(5000.0)
    dists = [ball[v] for v in search.order]
    assert all(a <= b for a, b in zip(dists, dists[1:]))


def test_csr_roundtrip(road):
    indptr, indices, weights = road.csr()
    assert indptr[-1] == sum(len(n) for n in road.adjacency)
    for v in range(road.num_vertices):
        nbrs = [(int(indices[i]), float(weights[i])) for i in range(indptr[v], indptr[v + 1])]
        assert nbrs == road.adjacency[v]


# --------------------------------------------------------------------- #
# Batched CSR relaxation == pure-Python Dijkstra (bit-exact in x64)      #
# --------------------------------------------------------------------- #
def test_spotlight_ball_ref_bit_exact_100_queries(road):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.kernels.spotlight_ball.ref import dense_adjacency, spotlight_ball_ref

    indptr, indices, weights = road.csr()
    rng = np.random.default_rng(0)
    Q = 100
    sources = rng.integers(0, road.num_vertices, size=Q).astype(np.int32)
    radii = rng.uniform(50.0, 2000.0, size=Q)

    with jax.enable_x64(True):
        W = jnp.asarray(dense_adjacency(indptr, indices, weights))
        D = np.asarray(spotlight_ball_ref(W, jnp.asarray(sources), jnp.asarray(radii)))

    for qi in range(Q):
        ball = road.weighted_ball(int(sources[qi]), float(radii[qi]))
        row = D[qi]
        inside = {v for v in range(road.num_vertices) if math.isfinite(row[v])}
        assert inside == set(ball), f"membership mismatch for query {qi}"
        for v, d in ball.items():
            assert row[v] == d, f"distance mismatch at query {qi}, vertex {v}"


def test_spotlight_ball_pallas_matches_ref(road):
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.spotlight_ball.kernel import relax_step_pallas
    from repro.kernels.spotlight_ball.ref import dense_adjacency, relax_step_ref

    indptr, indices, weights = road.csr()
    W = jnp.asarray(dense_adjacency(indptr, indices, weights.astype(np.float32)))
    rng = np.random.default_rng(1)
    Q = 16
    D = jnp.asarray(
        np.where(rng.uniform(size=(Q, road.num_vertices)) < 0.05, 0.0, np.inf).astype(
            np.float32
        )
    )
    for _ in range(3):
        ref_step = relax_step_ref(D, W)
        pallas_step = relax_step_pallas(D, W, interpret=True)
        np.testing.assert_array_equal(np.asarray(pallas_step), np.asarray(ref_step))
        D = ref_step


# --------------------------------------------------------------------- #
# Incremental TL strategies == original from-scratch behaviour           #
# --------------------------------------------------------------------- #
def test_wbfs_incremental_across_episodes(road):
    cams = {c: c for c in range(road.num_vertices)}
    incremental = TLWBFS(road, cams, entity_speed=4.0)
    for episode_start, cam in ((0.0, 10), (40.0, 55), (90.0, 10)):
        det = [Detection(camera_id=cam, positive=True, timestamp=episode_start)]
        incremental.update(det, now=episode_start)
        fresh = TLWBFS(road, cams, entity_speed=4.0)
        fresh.update(det, now=episode_start)
        for dt in (3.0, 9.0, 21.0, 33.0):
            now = episode_start + dt
            assert incremental.update([], now) == fresh.update([], now)


def test_multi_source_spotlight_dedupes_duplicate_sources():
    """Two queries sharing a blind-spot camera used to pad duplicate rows
    into the kernel call; duplicates must now collapse before dispatch
    (9 rows / 2 unique pairs -> the minimum bucket, never 16) while the
    returned per-query sets stay equal to independent singleton calls."""
    pytest.importorskip("jax")
    from repro.core.tracking import multi_source_spotlight
    from repro.kernels import dispatch

    net = make_road_network(num_vertices=160, target_edges=440, seed=11)
    cams = {c: c for c in range(net.num_vertices)}
    sources = [5, 5, 5, 80, 80, 5, 80, 5, 5]
    radii = [300.0] * len(sources)
    for coverage in (None, 0.9):
        out = multi_source_spotlight(net, cams, sources, radii, coverage=coverage)
        solo = {
            s: multi_source_spotlight(net, cams, [s], [300.0], coverage=coverage)[0]
            for s in (5, 80)
        }
        assert len(out) == len(sources)
        for s, got in zip(sources, out):
            assert got == solo[s] and got
    # Distinct set objects per row: mutating one must not leak into others.
    out[0].add(-1)
    assert -1 not in out[5]
    # Bucket accounting: this network only ever dispatched the minimum
    # bucket (2 unique pairs), never the bucket for 9 raw rows.
    shapes = {s for s in dispatch._SHAPES if s[0] == "ball" and s[1] == net.num_vertices}
    assert shapes and all(s[2] == dispatch.BUCKET_MIN for s in shapes)


def test_multi_entity_python_vs_kernel(road):
    pytest.importorskip("jax")
    cams = {c: c for c in range(road.num_vertices)}
    tl = TLProbabilistic(road, cams, entity_speed=4.0, coverage=0.9)
    tl.track("a", 10, 0.0)
    tl.track("b", 150, 2.0)
    tl.track("c", 77, 5.0)
    py = tl.spotlight_multi(30.0)
    kr = tl.spotlight_multi(30.0, use_kernel=True)
    assert py == kr
    assert py  # non-empty


# --------------------------------------------------------------------- #
# Scale regression: 10k cameras must stay cheap                         #
# --------------------------------------------------------------------- #
def test_10k_camera_scenario_under_wall_clock_ceiling():
    from repro.sim import ScenarioConfig, TrackingScenario

    t0 = time.time()
    cfg = ScenarioConfig(
        num_cameras=10_000,
        duration_s=10.0,
        fps=1.0,
        tl="bfs",
        batching="dynamic",
        m_max=25,
        seed=0,
    )
    res = TrackingScenario(cfg).run()
    wall = time.time() - t0
    assert res.source_events > 0
    assert res.peak_active < 10_000, "spotlight must not light up every camera"
    # Generous CI ceiling; the seed-era O(num_cameras)-per-tick loops plus
    # O(V^2)-memory road construction would blow far past this.
    assert wall < 60.0, f"10k-camera scenario took {wall:.1f}s"
