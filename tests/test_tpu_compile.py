"""Compile the main path's device programs for one TPU v5e chip, with no chip
attached.

The TPU compiler ships with jaxlib and compiles for a described ``v5e:2x2``
topology.  It refuses what interpret mode lets through: block shapes off the
(8, 128) tiling, 64-bit operands in a Pallas call, programs that do not fit
the device.  Each test lowers one program at the shapes of the paper's
deployment (1000 cameras, 300 s, 16 queries, 128-d re-ID embeddings) with
``interpret=False`` and the Pallas choice passed explicitly, since
``jax.default_backend()`` here is still ``cpu``: on one chip, and the
camera-sharded scan on the topology's four.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.  Compiles made here cannot be
read back from the persistent compilation cache without a chip, so the cache
is off while these tests run.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

# One v5e chip holds 16 GB of HBM.
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_cache):
    return SingleDeviceSharding(no_cache.devices[0])


@pytest.fixture(scope="module")
def four_chips(no_cache):
    from jax.sharding import Mesh

    return Mesh(np.array(no_cache.devices[:4]), ("cameras",))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


def test_megastep_chunk_compiles_for_v5e(one_chip):
    """The fused scan chunk at the plan of 1000 cameras / 300 s / 16 wbfs
    queries (seed 0): Cb=1024 cameras, Nb=16 queries, L=10 lanes, K=256
    ticks per chunk, Tb=512 table ticks, Gb=8 radius groups, NCb=8
    candidate rows, U=32 draws, S=8 slots, R=512 ring records.  The chain
    state is f64, so the jnp slot scan is the lane chain."""
    from repro.kernels.megastep import ops

    Cb, Nb, L, K, Tb, Gb, NCb, U, S, R = 1024, 16, 10, 256, 512, 8, 8, 32, 8, 512
    assert not ops.lane_chain_uses_pallas(np.float64)
    with jax.enable_x64(True):
        f64, i64, b = jnp.float64, jnp.int64, jnp.bool_
        sd = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
        carry = (
            sd((Nb, Cb), b), sd((Nb,), i64), sd((Nb,), i64),
            sd((L,), f64), sd((L,), b), sd((L,), f64), sd((L,), b),
            sd((L,), i64),
            sd((R,), b), sd((R,), f64), sd((R,), i64), sd((R,), i64),
            sd((R,), i64), sd((R,), b), sd((R, Nb), b),
            sd((), b), sd((), b),
        )
        tables = (
            sd((Cb,), i64), sd((U,), f64), sd((Nb,), jnp.int8),
            sd((Nb,), i64), sd((Gb, Tb, Tb), f64), sd((Gb, Tb, Tb), i64),
            sd((Cb,), i64), sd((NCb, Cb), f64), sd((NCb, Cb), i64),
            sd((Nb,), b), sd((Cb,), b), sd((S,), i64),
        )
        scalars = tuple(sd((), f64) for _ in range(7))
        compiled = ops._build_chunk_fn().lower(
            carry, sd((K,), f64), sd((K,), b), sd((K, Cb), b), sd((), i64),
            scalars, tables, use_pallas=False, interpret=False,
        ).compile()
    _fits_one_chip(compiled)


def test_sharded_chunk_compiles_for_v5e_2x2(four_chips):
    """The camera-sharded scan chunk on a 4-chip mesh at the same plan
    shapes.  The TPU lowers no 64-bit all-reduce but a sum, so the
    frontier's min/max collectives must stay 32-bit."""
    from repro.kernels.megastep import sharded

    Cb, Nb, L, K, Tb, Gb, NCb, U, S, R = 1024, 16, 10, 256, 512, 8, 8, 32, 8, 512
    cams, cam_cols = P("cameras"), P(None, "cameras")
    with jax.enable_x64(True):
        f64, i64, b = jnp.float64, jnp.int64, jnp.bool_

        def sd(shape, dt, spec=P()):
            return _sds(NamedSharding(four_chips, spec), shape, dt)

        carry = (
            sd((Nb, Cb), b, cam_cols), sd((Nb,), i64), sd((Nb,), i64),
            sd((L,), f64), sd((L,), b), sd((L,), f64), sd((L,), b),
            sd((L,), i64),
            sd((R,), b), sd((R,), f64), sd((R,), i64), sd((R,), i64),
            sd((R,), i64), sd((R,), b), sd((R, Nb), b),
            sd((), b), sd((), b), sd((Nb,), i64), sd((Nb,), i64),
        )
        tables = (
            sd((Cb,), i64, cams), sd((U,), f64), sd((Nb,), jnp.int8),
            sd((Nb,), i64), sd((Gb, Tb, Tb), f64), sd((Gb, Tb, Tb), i64),
            sd((Cb,), i64), sd((NCb, Cb), f64, cam_cols),
            sd((NCb, Cb), i64, cam_cols), sd((Nb,), b), sd((Cb,), b, cams),
            sd((S,), i64),
        )
        scalars = tuple(sd((), f64) for _ in range(7))
        compiled = sharded._build_sharded_chunk_fn(four_chips, "cameras").lower(
            carry, sd((K,), f64), sd((K,), b), sd((K, Cb), b, cam_cols),
            sd((), i64), scalars, tables,
        ).compile()
    assert "all-reduce" in compiled.as_text()
    _fits_one_chip(compiled)


def test_spotlight_relax_step_compiles_for_v5e(one_chip):
    """One min-plus relaxation over the 1000-vertex road graph of the
    1000-camera deployment (V padded to 1024 inside the kernel), for a
    16-row query bucket."""
    from repro.kernels.spotlight_ball.kernel import relax_step_pallas

    D = _sds(one_chip, (16, 1000), jnp.float32)
    W = _sds(one_chip, (1000, 1000), jnp.float32)
    compiled = jax.jit(
        lambda d, w: relax_step_pallas(d, w, interpret=False)
    ).lower(D, W).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_spotlight_ball_dispatch_compiles_for_v5e(one_chip):
    """The bucket-padded fixpoint that ``TLProbabilistic.spotlight_multi(
    use_kernel=True)`` dispatches, with the Pallas step chosen."""
    from repro.kernels import dispatch

    ball = dispatch._make_ball_padded()
    compiled = ball.lower(
        _sds(one_chip, (1000, 1000), jnp.float32),
        _sds(one_chip, (16,), jnp.int32),
        _sds(one_chip, (16,), jnp.float32),
        use_pallas=True, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_reid_match_kernel_compiles_for_v5e(one_chip):
    """The re-ID matcher kernel at the App 4 embedding width (128)."""
    from repro.kernels.reid_match.kernel import reid_match_pallas

    compiled = jax.jit(
        lambda g, q: reid_match_pallas(g, q, threshold=0.5, interpret=False)
    ).lower(
        _sds(one_chip, (4096, 128), jnp.float32),
        _sds(one_chip, (8, 128), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_reid_match_multi_compiles_for_v5e(one_chip):
    """The padded query-major matcher a multi-query run's VA batches are
    answered by: the 64-row gallery bucket a flush launches (VA batches hold
    at most m_max=25 frames) against 16 queries at embed_dim=128."""
    from repro.kernels import dispatch

    rows = dispatch.REID_MULTI_ROWS
    compiled = dispatch._make_reid_multi_padded().lower(
        _sds(one_chip, (rows, 128), jnp.float32),
        _sds(one_chip, (16, 128), jnp.float32),
        _sds(one_chip, (rows, 16), jnp.bool_),
    ).compile()
    _fits_one_chip(compiled)
