"""Property + compile-count tests for the fused mega-step engine.

The fuzz half (requires the optional ``hypothesis`` dependency, skipped
cleanly when missing) hammers the bit-exactness gate over random small
configs: whatever TL mix / warm start / duration hypothesis draws, the
fused run must equal the interpreted pipeline *exactly* — not "close", not
per-summary, but deep-equal on every observable book.

The compile-count half pins the dispatch contract: one world geometry run
repeatedly (and chunked over multiple K-tick dispatches) compiles the scan
at most once per bucket shape, the shape is accounted in
``dispatch.jit_cache_sizes()``, and the Pallas lane-chain kernel (interpret
mode: with f64 operands it compiles for no chip) is bit-equal to the jnp
inner scan the engine runs.
"""

import copy

import numpy as np
import pytest

from repro.kernels import dispatch
from repro.query import MultiQueryScenario, QuerySpec
from repro.sim import ScenarioConfig


def _fixed_cfg(**kw):
    base = dict(num_cameras=60, duration_s=60.0, seed=0, tl="bfs",
                batching="dynamic", m_max=25)
    base.update(kw)
    return ScenarioConfig(**base)


def _pair(cfg, specs):
    a = MultiQueryScenario(copy.deepcopy(cfg), copy.deepcopy(specs)).run()
    c = copy.deepcopy(cfg)
    c.engine = "megastep"
    scn = MultiQueryScenario(c, copy.deepcopy(specs))
    b = scn.run()
    return a, b, scn


def _books(res):
    out = {
        "global": res.result.summary(),
        "lat": res.result.latencies,
        "active": res.result.active_timeline,
        "per": {qid: res.per_query_summary(qid) for qid in res.per_query},
    }
    for qid in res.per_query:
        st = res.registry.get(qid)
        out[("ctrl", qid)] = (sorted(st.requested), sorted(st.applied))
    return out


# --------------------------------------------------------------------- #
# Compile-count: at most one compile per (bucket, K) shape               #
# --------------------------------------------------------------------- #
def test_scan_compiles_once_per_bucket_shape():
    """Two different seeds/TL mixes on the same world geometry hit the same
    bucket shape: the second run must not add a compilation, and the shape
    must show up in the shared jit-cache accounting."""
    specs_a = [QuerySpec(tl="wbfs"), QuerySpec(tl="bfs")]
    specs_b = [QuerySpec(tl="bfs", tl_peak_speed=6.0), QuerySpec(tl="base"),
               QuerySpec(tl="wbfs", last_seen_camera=11)]

    _, _, scn = _pair(_fixed_cfg(), specs_a)
    assert scn.engine_used == "megastep-device", scn.engine_fallback_reason
    sizes0 = dispatch.jit_cache_sizes()["megastep"]
    assert sizes0 >= 1

    # duration 60 -> T=61 ticks -> two K=64 dispatches would need T>64;
    # same geometry, different query mix and seed: same bucket shape.
    _, _, scn = _pair(_fixed_cfg(seed=3), specs_b)
    assert scn.engine_used == "megastep-device"
    assert dispatch.jit_cache_sizes()["megastep"] == sizes0

    # A longer run spans multiple K-tick chunks of the SAME shape (k0 is a
    # traced scalar): still no new compilation beyond its own (T-bucket)
    # shape, and repeating it adds nothing.
    _, _, scn = _pair(_fixed_cfg(duration_s=150.0), specs_a)
    assert scn.engine_used == "megastep-device"
    grown = dispatch.jit_cache_sizes()["megastep"]
    _, _, scn = _pair(_fixed_cfg(duration_s=150.0, seed=4), specs_b)
    assert scn.engine_used == "megastep-device"
    assert dispatch.jit_cache_sizes()["megastep"] == grown


def test_megastep_cache_is_bounded():
    """The scan shares the bounded-jit-cache contract with every other
    padded kernel: its LRU is registered under the "megastep" key."""
    specs = [QuerySpec(tl="wbfs")]
    _, _, scn = _pair(_fixed_cfg(), specs)
    assert scn.engine_used == "megastep-device", scn.engine_fallback_reason
    assert "megastep" in dispatch._JIT_LRU
    assert len(dispatch._JIT_LRU["megastep"]) <= dispatch.MAX_JIT_SHAPES


# --------------------------------------------------------------------- #
# Pallas lane-chain kernel == jnp inner scan (interpret mode)             #
# --------------------------------------------------------------------- #
def test_pallas_lane_chain_matches_jnp_scan():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.kernels.megastep.kernel import lane_chain_tick_pallas

    rng = np.random.default_rng(7)
    L, S, U = 4, 8, 32
    with jax.enable_x64(True):
        real = rng.random((L, S)) < 0.6
        has = rng.random((L, S)) < 0.5
        va_b = rng.uniform(0.0, 3.0, L)
        va_armed = rng.random(L) < 0.5
        cr_b = rng.uniform(0.0, 3.0, L)
        cr_armed = rng.random(L) < 0.5
        draws = rng.integers(0, U // 2, L)
        uniforms = rng.uniform(size=U)
        t_arr, xi_va, xi_cr = 1.25, 0.03125, 0.0625
        d_vc, d_cu, p_tp = 0.001953125, 0.015625, 0.9
        params = jnp.asarray([t_arr, xi_va, xi_cr, d_vc, d_cu, p_tp])

        got = lane_chain_tick_pallas(
            jnp.asarray(real), jnp.asarray(has), jnp.asarray(va_b),
            jnp.asarray(va_armed), jnp.asarray(cr_b), jnp.asarray(cr_armed),
            jnp.asarray(draws), jnp.asarray(uniforms), params,
            # f64 operands: the kernel compiles for no chip, only interprets.
            interpret=True,
        )

        # The jnp reference: the exact slot_step scan from ops._build_chunk_fn.
        def slot_step(cc, s):
            b_v, a_v, b_c, a_c, dr = cc
            r = jnp.asarray(real)[:, s]
            h = jnp.asarray(has)[:, s]
            fu_v = t_arr >= b_v
            st_v = jnp.where(a_v, b_v, t_arr + (b_v - t_arr))
            end_v = jnp.where(fu_v, t_arr + xi_va, st_v + xi_va)
            q_v = jnp.where(fu_v, 0.0, st_v - t_arr)
            b_v = jnp.where(r, end_v, b_v)
            a_v = jnp.where(r, ~fu_v, a_v)
            arr_c = end_v + d_vc
            fu_c = arr_c >= b_c
            st_c = jnp.where(a_c, b_c, arr_c + (b_c - arr_c))
            end_c = jnp.where(fu_c, arr_c + xi_cr, st_c + xi_cr)
            q_c = jnp.where(fu_c, 0.0, st_c - arr_c)
            b_c = jnp.where(r, end_c, b_c)
            a_c = jnp.where(r, ~fu_c, a_c)
            u = jnp.asarray(uniforms)[jnp.minimum(dr, U - 1)]
            drawn = r & h
            p = drawn & (u <= p_tp)
            dr = dr + drawn
            return (b_v, a_v, b_c, a_c, dr), (
                end_v, q_v, fu_v, end_c, q_c, fu_c, end_c + d_cu, p
            )

        carry0 = (jnp.asarray(va_b), jnp.asarray(va_armed),
                  jnp.asarray(cr_b), jnp.asarray(cr_armed),
                  jnp.asarray(draws))
        want_carry, so = jax.lax.scan(
            slot_step, carry0, jnp.arange(S, dtype=jnp.int64)
        )
        want = want_carry + tuple(x.T for x in so)

        assert len(got) == len(want)
        for g, w in zip(got, want):
            gh, wh = np.asarray(g), np.asarray(w)
            assert gh.dtype == wh.dtype or gh.dtype == np.bool_
            np.testing.assert_array_equal(gh, wh)


# --------------------------------------------------------------------- #
# Hypothesis fuzz: fused == interpreted on random small configs           #
# --------------------------------------------------------------------- #
# The compile-count / Pallas tests above must run even without the
# optional dependency, so only the fuzz half skips.
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @st.composite
    def small_runs(draw):
        cams = draw(st.sampled_from([40, 60]))
        cfg = dict(
            num_cameras=cams,
            duration_s=draw(st.sampled_from([30.0, 45.0, 60.0])),
            seed=draw(st.integers(0, 3)),
            tl="bfs",
            batching=draw(st.sampled_from(["dynamic", "static"])),
            m_max=25,
        )
        if cfg["batching"] == "static":
            cfg["static_batch"] = 1
        n = draw(st.integers(1, 3))
        specs = []
        for _ in range(n):
            specs.append(QuerySpec(
                tl=draw(st.sampled_from(["base", "bfs", "wbfs"])),
                tl_peak_speed=draw(st.one_of(st.none(),
                                             st.sampled_from([3.0, 6.0]))),
                last_seen_camera=draw(st.one_of(st.none(),
                                                st.integers(0, cams - 1))),
            ))
        return cfg, specs

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(run=small_runs())
    def test_fused_is_bit_equal_to_interpreted(run):
        cfg_kw, specs = run
        cfg = ScenarioConfig(**cfg_kw)
        a, b, scn = _pair(cfg, specs)
        # Whatever backend the draw lands on (device, or host past a
        # capacity divergence), the books must be bit-identical.
        assert scn.engine_used.startswith("megastep-"), (
            scn.engine_fallback_reason
        )
        assert _books(a) == _books(b)
