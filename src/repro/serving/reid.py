"""Re-identification tower: the CR stage's embedding model.

A compact residual MLP mapping frame feature vectors to L2-normalizable
embeddings; matching runs through the dispatch plane's re-ID matcher
(``repro.kernels.dispatch.reid_match_multi``).
This is the JAX analogue of the paper's OpenReid DNN in CR (App 1) and the
small/large re-id pair of App 4.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.models.layers import Params, init_linear, init_norm, linear, rms_norm

__all__ = ["init_reid_tower", "embed_frames", "match"]


def init_reid_tower(
    key: jax.Array, d_in: int = 128, d_hidden: int = 256, d_embed: int = 64, depth: int = 2
) -> Params:
    ks = jax.random.split(key, depth + 2)
    return {
        "proj_in": init_linear(ks[0], d_in, d_hidden),
        "blocks": [
            {
                "norm": init_norm(d_hidden),
                "w1": init_linear(ks[i + 1], d_hidden, d_hidden),
                "w2": init_linear(jax.random.fold_in(ks[i + 1], 1), d_hidden, d_hidden),
            }
            for i in range(depth)
        ],
        "proj_out": init_linear(ks[-1], d_hidden, d_embed),
    }


@jax.jit
def embed_frames(params: Params, frames: jax.Array) -> jax.Array:
    """frames: (N, d_in) -> embeddings (N, d_embed)."""
    x = linear(params["proj_in"], frames)
    for blk in params["blocks"]:
        h = rms_norm(blk["norm"], x)
        h = jax.nn.silu(linear(blk["w1"], h))
        x = x + linear(blk["w2"], h)
    return linear(params["proj_out"], x)


def match(
    params: Params, frames: jax.Array, queries: jax.Array, threshold: float = 0.5
) -> Tuple[np.ndarray, np.ndarray]:
    """Full CR stage: embed candidate frames, match against query embeddings.
    Returns host ``(scores, matched)`` of shape ``(N, Q)``."""
    gallery = embed_frames(params, frames)
    scores, matched = dispatch.reid_match_multi(gallery, queries, threshold=threshold)
    return np.asarray(scores), np.asarray(matched)
