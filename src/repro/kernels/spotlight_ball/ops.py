"""jit-ready wrapper for the batched spotlight-ball search (see flash ops).

``spotlight_ball(indptr, indices, weights, sources, radii)`` relaxes a batch
of Q query balls over the CSR road graph and returns (Q, V) distances with
``inf`` outside each query's radius.  Backend selection mirrors
``reid_match``: the dense min-plus fixpoint runs through the Pallas kernel on
TPU (or when forced via ``REPRO_FORCE_PALLAS=1``, interpreted off-TPU) and
through the pure-jnp reference otherwise.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import pallas_interpret
from .ref import dense_adjacency, relax_step_ref, spotlight_ball_ref

__all__ = ["spotlight_ball"]


def _use_pallas() -> bool:
    force = os.environ.get("REPRO_FORCE_PALLAS", "")
    if force == "1":
        return True
    if force == "0":
        return False
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def _iterate_pallas(W: jax.Array, D0: jax.Array, radii: jax.Array, *, interpret: bool):
    from .kernel import relax_step_pallas

    V = W.shape[0]

    def cond(state):
        D, changed, it = state
        return jnp.logical_and(changed, it < V)

    def body(state):
        D, _, it = state
        Dn = relax_step_pallas(D, W, interpret=interpret)
        return Dn, jnp.any(Dn < D), it + 1

    D, _, _ = jax.lax.while_loop(cond, body, (D0, jnp.bool_(True), jnp.int32(0)))
    inf = jnp.array(jnp.inf, dtype=D.dtype)
    return jnp.where(D <= radii[:, None], D, inf)


def spotlight_ball(
    indptr,
    indices,
    weights,
    sources,
    radii,
) -> jax.Array:
    """Batched Dijkstra balls over a CSR graph.

    Parameters are CSR arrays (``indptr`` (V+1,), ``indices``/``weights``
    (E,)) plus per-query ``sources`` (Q,) and ``radii`` (Q,).  Returns a
    (Q, V) distance matrix in the weights' dtype, ``inf`` where a vertex is
    unreachable or outside the query's radius.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    weights = np.asarray(weights)
    W = jnp.asarray(dense_adjacency(indptr, indices, weights))
    sources = jnp.asarray(sources, dtype=jnp.int32)
    radii = jnp.asarray(radii, dtype=W.dtype)
    if _use_pallas():
        Q, V = sources.shape[0], W.shape[0]
        inf = jnp.array(jnp.inf, dtype=W.dtype)
        D0 = jnp.full((Q, V), inf, dtype=W.dtype)
        D0 = D0.at[jnp.arange(Q), sources].set(jnp.zeros((), dtype=W.dtype))
        return _iterate_pallas(
            W, D0, radii, interpret=pallas_interpret()
        )
    return spotlight_ball_ref(W, sources, radii)
