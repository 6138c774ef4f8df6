"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_chunked_ref, attention_ref
from repro.kernels import dispatch
from repro.kernels.reid_ref import reid_match_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_decode_step_ref, ssd_ref

KEY = jax.random.PRNGKey(0)


def tol_for(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


# --------------------------------------------------------------------- #
# flash attention                                                        #
# --------------------------------------------------------------------- #
FLASH_CASES = [
    # (B, S, T, Hq, Hkv, D, causal, window, q_offset, dtype)
    (2, 128, 128, 4, 2, 64, True, 0, 0, jnp.float32),
    (1, 200, 200, 5, 5, 64, True, 0, 0, jnp.float32),     # odd heads/len
    (2, 256, 256, 4, 1, 128, True, 64, 0, jnp.bfloat16),  # MQA + window
    (1, 64, 192, 2, 2, 32, True, 0, 128, jnp.float32),    # continuation
    (1, 128, 128, 2, 2, 64, False, 0, 0, jnp.float32),    # bidirectional
    (1, 96, 96, 4, 2, 48, True, 0, 0, jnp.bfloat16),      # Dv == D != mult of 128
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(i) for i in range(len(FLASH_CASES))])
def test_flash_pallas_matches_ref(case):
    B, S, T, Hq, Hkv, D, causal, window, qo, dt = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dt)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dt)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dt)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=qo)
    got = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=qo,
        block_q=64, block_k=64, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=tol_for(dt)
    )


def test_flash_pallas_mla_value_dim():
    """MLA: qk head dim 192, value head dim 128."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 96))
    k = jax.random.normal(ks[1], (1, 128, 4, 96))
    v = jax.random.normal(ks[2], (1, 128, 4, 64))
    ref = attention_ref(q, k, v, causal=True)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_chunked_ref_matches_dense_ref():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 130, 4, 48))
    k = jax.random.normal(ks[1], (2, 130, 2, 48))
    v = jax.random.normal(ks[2], (2, 130, 2, 32))
    a = attention_ref(q, k, v, causal=True, window=40)
    b = attention_chunked_ref(q, k, v, causal=True, window=40, q_block=32, kv_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# --------------------------------------------------------------------- #
# decode attention                                                       #
# --------------------------------------------------------------------- #
DECODE_CASES = [
    (2, 256, 4, 2, 64, 0, jnp.float32),
    (3, 300, 8, 8, 64, 0, jnp.float32),
    (2, 512, 4, 1, 128, 128, jnp.bfloat16),
    (1, 128, 2, 2, 32, 0, jnp.float32),
]


@pytest.mark.parametrize("case", DECODE_CASES, ids=[str(i) for i in range(len(DECODE_CASES))])
def test_decode_pallas_matches_ref(case):
    B, T, Hq, Hkv, D, window, dt = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dt)
    # head-major cache layout (B, Hkv, T, D) — §Perf H3
    k = jax.random.normal(ks[1], (B, Hkv, T, D), dt)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), dt)
    length = jax.random.randint(ks[3], (B,), 1, T + 1)
    ref = decode_attention_ref(q, k, v, length, window=window)
    got = decode_attention_pallas(q, k, v, length, window=window, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=tol_for(dt)
    )


# --------------------------------------------------------------------- #
# SSD scan                                                               #
# --------------------------------------------------------------------- #
SSD_CASES = [
    (2, 128, 4, 32, 1, 16, 32, False),
    (1, 96, 8, 16, 2, 32, 32, True),
    (1, 100, 4, 16, 1, 16, 32, False),  # ragged length
    (2, 64, 2, 64, 1, 64, 64, True),
]


@pytest.mark.parametrize("case", SSD_CASES, ids=[str(i) for i in range(len(SSD_CASES))])
def test_ssd_pallas_matches_ref(case):
    B, L, H, P, G, N, chunk, init = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, G, N)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.3 if init else None
    y_ref, fs_ref = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk, initial_state=s0)
    y_got, fs_got = ssd_scan_pallas(
        x, dt, A, Bm, Cm, chunk=chunk, initial_state=s0, interpret=True
    )
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(fs_got), np.asarray(fs_ref), atol=2e-4)


def test_ssd_decode_step_matches_scan():
    """One recurrent step == scan over a length-1 sequence."""
    B, H, P, G, N = 2, 4, 16, 1, 16
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, 1, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, 1, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, 1, G, N))
    Cm = jax.random.normal(ks[4], (B, 1, G, N))
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.3
    y_scan, fs_scan = ssd_ref(x, dt, A, Bm, Cm, chunk=1, initial_state=s0)
    y_step, fs_step = ssd_decode_step_ref(s0, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_scan[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(fs_step), np.asarray(fs_scan), atol=1e-5)


# --------------------------------------------------------------------- #
# reid match                                                             #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("N,Q,D,thr", [(100, 3, 64, 0.5), (257, 1, 128, 0.3), (64, 8, 32, 0.9)])
def test_reid_matcher_matches_ref(N, Q, D, thr):
    """The dispatch plane's matcher against the plain reference: the best
    score per gallery row agrees to 2e-6, and the flags agree wherever the
    score is not within reach of the threshold."""
    g = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 1), (N, D)))
    q = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 2), (Q, D)))
    s_ref, _, m_ref = [np.asarray(x) for x in reid_match_ref(g, q, threshold=thr)]
    scores, matched = [np.asarray(x) for x in dispatch.reid_match_multi(g, q, threshold=thr)]
    assert scores.shape == matched.shape == (N, Q)
    np.testing.assert_allclose(scores.max(axis=1), s_ref, rtol=0, atol=2e-6)
    clear = np.abs(s_ref - thr) > 1e-5
    assert np.array_equal(matched.any(axis=1)[clear], m_ref[clear])


def test_reid_match_finds_planted_target():
    """A gallery row equal to the query must match with score ~1."""
    g = jax.random.normal(KEY, (50, 64))
    q = g[17:18] * 2.0  # same direction
    s, b, m = reid_match_ref(g, q, threshold=0.99)
    assert bool(m[17])
    assert int(jnp.argmax(s)) == 17
