"""Pure-jnp oracle for the re-identification matcher (the CR hot loop).

Given a gallery of candidate embeddings (detections cropped from frames) and
one or more query embeddings (the entity, possibly fused by QF), compute
L2-normalized cosine similarities and per-candidate best-query scores.  The
Pallas kernel tiles the gallery over VMEM; this is its ground truth.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["reid_match_ref"]


def reid_match_ref(
    gallery: jax.Array,  # (N, D) candidate embeddings
    queries: jax.Array,  # (Q, D) entity query embeddings
    *,
    threshold: float = 0.5,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(scores, best_query, is_match)``:
    scores (N,) best cosine similarity, best_query (N,) argmax query index,
    is_match (N,) bool score >= threshold."""
    g = gallery.astype(jnp.float32)
    q = queries.astype(jnp.float32)
    g = g / jnp.maximum(jnp.linalg.norm(g, axis=-1, keepdims=True), 1e-6)
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)
    # HIGHEST: a TPU runs a default-precision f32 matmul as bf16 passes.
    sim = jnp.matmul(g, q.T, precision=jax.lax.Precision.HIGHEST)  # (N, Q)
    scores = jnp.max(sim, axis=-1)
    best = jnp.argmax(sim, axis=-1).astype(jnp.int32)
    return scores, best, scores >= threshold
