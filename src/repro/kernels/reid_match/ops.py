"""jit-ready wrapper for the re-id matcher (see flash ops)."""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax

from ..dispatch import pallas_interpret
from .ref import reid_match_ref

__all__ = ["reid_match"]


def _use_pallas() -> bool:
    force = os.environ.get("REPRO_FORCE_PALLAS", "")
    if force == "1":
        return True
    if force == "0":
        return False
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("threshold",))
def reid_match(
    gallery: jax.Array, queries: jax.Array, *, threshold: float = 0.5
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    if _use_pallas():
        from .kernel import reid_match_pallas

        return reid_match_pallas(
            gallery, queries, threshold=threshold,
            interpret=pallas_interpret(),
        )
    return reid_match_ref(gallery, queries, threshold=threshold)
