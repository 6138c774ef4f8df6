"""Context-parallel decode attention: LSE-combine correctness.

The combine identity is checked single-host (pure math), and the full
shard_map path runs in a subprocess with 8 emulated devices.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.context_parallel import combine_partials, decode_attention_partial
from repro.kernels.decode_attention.ref import decode_attention_ref

KEY = jax.random.PRNGKey(0)


def test_partial_plus_combine_equals_reference():
    """Splitting the cache into local shards and LSE-combining the partials
    must reproduce the monolithic softmax exactly."""
    B, Hq, Hkv, T, D, S = 2, 4, 2, 96, 32, 4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, Hkv, T, D))
    v = jax.random.normal(ks[2], (B, Hkv, T, D))
    length = jnp.asarray([70, 33], jnp.int32)
    ref = decode_attention_ref(q, k, v, length)

    T_loc = T // S
    outs, ms, ls = [], [], []
    for i in range(S):
        k_l = k[:, :, i * T_loc : (i + 1) * T_loc]
        v_l = v[:, :, i * T_loc : (i + 1) * T_loc]
        pos = i * T_loc + jnp.arange(T_loc)[None, :]
        valid = pos < length[:, None]
        o, m, l = decode_attention_partial(q, k_l, v_l, valid, scale=D ** -0.5)
        outs.append(o), ms.append(m), ls.append(l)
    got = combine_partials(jnp.stack(outs), jnp.stack(ms), jnp.stack(ls))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref, np.float32), atol=1e-5)


def test_empty_shards_are_safe():
    """Shards entirely past `length` contribute exp(-inf)=0, not NaN."""
    B, Hq, Hkv, T, D = 1, 2, 2, 32, 16
    q = jax.random.normal(KEY, (B, Hq, D))
    k = jax.random.normal(KEY, (B, Hkv, T, D))
    v = jax.random.normal(KEY, (B, Hkv, T, D))
    length = jnp.asarray([8], jnp.int32)  # second half of cache invalid
    o1, m1, l1 = decode_attention_partial(
        q, k[:, :, :16], v[:, :, :16],
        (jnp.arange(16)[None] < length[:, None]), scale=D ** -0.5,
    )
    o2, m2, l2 = decode_attention_partial(
        q, k[:, :, 16:], v[:, :, 16:],
        (16 + jnp.arange(16)[None] < length[:, None]), scale=D ** -0.5,
    )
    got = combine_partials(jnp.stack([o1, o2]), jnp.stack([m1, m2]), jnp.stack([l1, l2]))
    assert bool(jnp.all(jnp.isfinite(got)))
    ref = decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref, np.float32), atol=1e-5)


SHARD_MAP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.context_parallel import context_parallel_decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, T, D = 2, 4, 2, 128, 32
    q = jax.random.normal(key, (B, Hq, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, T, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, T, D))
    length = jnp.asarray([100, 47], jnp.int32)
    got = context_parallel_decode_attention(mesh, "data", q, k, v, length)
    ref = decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref, np.float32), atol=1e-5)
    # the lowered program must NOT all-gather the cache: biggest collective
    # should be the (S,B,Hq,D)-ish stats gather, far below cache size.
    txt = jax.jit(lambda *a: context_parallel_decode_attention(mesh, "data", *a)) \
        .lower(q, k, v, length).compile().as_text()
    import re
    gathers = re.findall(r"all-gather[^=]*", txt)
    print("SHARD_MAP_CP_OK", len(gathers))
    """
)


def test_shard_map_context_parallel_8dev():
    res = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SCRIPT],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        timeout=420,
    )
    assert "SHARD_MAP_CP_OK" in res.stdout, res.stdout + res.stderr
