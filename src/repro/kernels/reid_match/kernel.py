"""Pallas TPU kernel for re-identification matching (CR hot loop).

Grid ``(n_gallery_blocks,)``: each step loads a (block_n, D) tile of
candidate embeddings into VMEM, L2-normalizes it, matmuls against the
(Q, D) query tile (kept resident — Q is small: one entity plus QF-fused
variants), and emits per-candidate best score / best query / match flag.

The queries are invariant across gallery tiles, so their L2-normalization
is hoisted out of the grid: ``ops.py`` normalizes once and the kernel
consumes pre-normalized queries (one rsqrt+mul per query total instead of
one per tile).

One MXU pass per tile; the gallery streams through VMEM once, so the
kernel is bandwidth-bound at ~D bytes per candidate — the right regime for
CR, which must score every active camera's detections each frame.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["reid_match_pallas"]


def _kernel(
    g_ref,  # (block_n, D)
    q_ref,  # (Q, D) — pre-normalized by the caller (invariant across tiles)
    score_ref,  # (block_n,)
    best_ref,  # (block_n,)
    match_ref,  # (block_n,)
    *,
    threshold: float,
):
    g = g_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)
    g = g / jnp.maximum(
        jnp.sqrt(jnp.sum(g * g, axis=1, keepdims=True)), 1e-6
    )
    sim = jax.lax.dot_general(
        g, q, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (block_n, Q)
    scores = jnp.max(sim, axis=1)
    best = jnp.argmax(sim, axis=1).astype(jnp.int32)
    score_ref[...] = scores
    best_ref[...] = best
    match_ref[...] = scores >= threshold


def reid_match_pallas(
    gallery: jax.Array,  # (N, D)
    queries: jax.Array,  # (Q, D)
    *,
    threshold: float = 0.5,
    block_n: int = 1024,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    N, D = gallery.shape
    Q = queries.shape[0]
    block_n = min(block_n, N)
    pad = (-N) % block_n
    if pad:
        gallery = jnp.pad(gallery, ((0, pad), (0, 0)))
    Np = gallery.shape[0]

    # Hoisted out of the grid: the query tile is identical for every gallery
    # block, so normalize once here instead of once per grid step.
    queries = queries.astype(jnp.float32)
    queries = queries / jnp.maximum(
        jnp.sqrt(jnp.sum(queries * queries, axis=1, keepdims=True)), 1e-6
    )

    kernel = functools.partial(_kernel, threshold=threshold)
    scores, best, is_match = pl.pallas_call(
        kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((Q, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np,), jnp.float32),
            jax.ShapeDtypeStruct((Np,), jnp.int32),
            jax.ShapeDtypeStruct((Np,), jnp.bool_),
        ],
        interpret=interpret,
    )(gallery, queries)
    return scores[:N], best[:N], is_match[:N]
