"""Bucket-batched kernel dispatch (the sweep engine's analytics plane).

``reid_match`` and ``spotlight_ball`` are called with whatever batch size
the simulation happens to produce — a fresh jit specialization per (Q, N)
pair means a sweep of scenarios recompiles the same kernels over and over.
This layer makes kernel launches sweep-friendly:

* **bucketing** — batch dimensions are padded up to power-of-two buckets
  (minimum :data:`BUCKET_MIN`), so an entire sweep compiles each kernel at
  most once per bucket shape.  Padding is masked out: spotlight pad rows
  get radius ``-1`` -> all-``inf`` and the min-plus relaxation is
  row-independent, so spotlight results are **bitwise** equal to the
  unpadded call; re-id pad queries are masked to ``-inf`` similarity, but
  padding the gallery changes the GEMM blocking, so re-id scores agree
  with the unpadded call only up to last-ulp reassociation (still fully
  deterministic for a given shape).
* **device-resident operands** — the dense min-plus adjacency of a road
  network and re-id query blocks are uploaded once and cached by operand
  identity (weakly referenced, so a dropped world frees its buffers).
  Per-call padded scratch operands of the single-query and spotlight
  kernels are donated to the kernel.
* **queued re-id** — ``reid_match_multi`` queues its call and returns
  handles; the first read of a queued answer answers the whole queue with
  one device launch per query block (scratch operands as host arrays), and
  cuts each call's answer out on the host.
* **cache-miss accounting** — :func:`stats` counts calls and distinct
  bucket shapes, and :func:`jit_cache_sizes` exposes the underlying jit
  caches so tests can assert "at most one compile per bucket shape".

Backend selection mirrors the kernel packages: Pallas on TPU (or when
``REPRO_FORCE_PALLAS=1``, interpreted off-TPU), pure-jnp reference
otherwise.
"""

from __future__ import annotations

import functools
import os
import weakref
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.clock import span

__all__ = [
    "BUCKET_MIN",
    "MAX_JIT_SHAPES",
    "REID_MULTI_ROWS",
    "bucket",
    "spotlight_ball",
    "reid_match",
    "reid_match_multi",
    "reid_flush",
    "reid_queued",
    "ReidAnswer",
    "stats",
    "reset_stats",
    "profile",
    "jit_cache_sizes",
    "bound_jit_cache",
    "pallas_interpret",
    "enable_compile_cache",
]

BUCKET_MIN = 8

# Upper bound on compiled specializations retained per padded kernel.  A
# sweep grid that walks many (bucket, dtype) shapes would otherwise grow
# each kernel's jit cache without bound; jit caches cannot evict single
# entries, so on overflow the kernel's whole cache is dropped and the next
# dispatch recompiles (LRU bookkeeping keeps that rare: only a sweep
# cycling through > MAX_JIT_SHAPES live shapes ever pays it).
MAX_JIT_SHAPES = 32

_STATS = {
    "reid_calls": 0,
    "reid_multi_calls": 0,
    "reid_multi_launches": 0,
    "ball_calls": 0,
    "device_cache_hits": 0,
    "device_cache_misses": 0,
    "bucket_shapes": 0,
}
_SHAPES: set = set()

# Observability profile (repro.obs.collect_dispatch): per-kernel distinct
# bucket-shape compiles.
_COMPILES: Dict[str, int] = {}


def bucket(n: int, minimum: int = BUCKET_MIN) -> int:
    """Smallest power-of-two >= ``n`` (and >= ``minimum``)."""
    if n < 1:
        raise ValueError(f"bucket size needs n >= 1, got {n}")
    return max(1 << (int(n) - 1).bit_length(), minimum)


def stats() -> Dict[str, int]:
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0
    _SHAPES.clear()
    _COMPILES.clear()


def profile() -> Dict[str, Dict[str, int]]:
    """Kernel-plane profile: per-kernel distinct bucket-shape compile
    counts (each new shape is one XLA compile of that kernel).  Host time
    inside the dispatch entry points is in the ``repro.reid.*`` spans of a
    JAX profiler trace (``repro.core.clock.SPANS``)."""
    return {"compiles": dict(_COMPILES)}


def _note_shape(key: Tuple) -> None:
    if key not in _SHAPES:
        _SHAPES.add(key)
        _STATS["bucket_shapes"] += 1
        name = str(key[0])
        _COMPILES[name] = _COMPILES.get(name, 0) + 1


# Per-kernel LRU of live bucket shapes, bounding the jit caches.
_JIT_LRU: Dict[str, "OrderedDict[Tuple, None]"] = {}


def bound_jit_cache(name: str, fn, key: Tuple, cap: Optional[int] = None) -> None:
    """Record that ``fn`` (a jitted kernel) is about to be dispatched with
    bucket-shape ``key``; when more than ``cap`` distinct shapes are live,
    drop the kernel's compile cache so it is rebuilt for the working set.

    Shared by every padded kernel here and by the mega-step engine's
    per-(bucket, K) compile cache, so "jit caches stay bounded" is one
    invariant with one implementation.
    """
    if cap is None:
        cap = MAX_JIT_SHAPES  # read at call time so tests can shrink it
    lru = _JIT_LRU.setdefault(name, OrderedDict())
    if key in lru:
        lru.move_to_end(key)
        return
    lru[key] = None
    if len(lru) > cap:
        fn.clear_cache()
        lru.clear()
        lru[key] = None


def _use_pallas() -> bool:
    import jax

    force = os.environ.get("REPRO_FORCE_PALLAS", "")
    if force == "1":
        return True
    if force == "0":
        return False
    return jax.default_backend() == "tpu"


# The checkout root (``src/repro/kernels/dispatch.py`` -> three up).
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left as it is.  Otherwise the cache goes to ``<checkout>/.jax_cache``,
    one fixed path, so every process run from this checkout shares it.
    Entry points call this before their first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_interpret() -> bool:
    """Pallas kernels run interpreted everywhere but on a TPU, where they
    always compile (Mosaic)."""
    import jax

    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------- #
# Device-resident operand cache (weak, keyed by host-array identity)      #
# --------------------------------------------------------------------- #
# id(array) -> (weakref to the host array, device buffer).  The weakref
# callback evicts the entry when the host array dies, which also guards
# against id() reuse.
_DEVICE_CACHE: Dict[int, Tuple[weakref.ref, object]] = {}


def _device_resident(arr: np.ndarray, transform=None):
    """``jax.device_put(transform(arr))`` memoized on the identity of
    ``arr`` (``transform``, e.g. bucket padding, runs only on a miss)."""
    import jax

    key = id(arr)
    entry = _DEVICE_CACHE.get(key)
    if entry is not None and entry[0]() is arr:
        _STATS["device_cache_hits"] += 1
        return entry[1]
    _STATS["device_cache_misses"] += 1
    dev = jax.device_put(transform(arr) if transform is not None else arr)

    def _evict(_ref, key=key):
        _DEVICE_CACHE.pop(key, None)

    _DEVICE_CACHE[key] = (weakref.ref(arr, _evict), dev)
    return dev


# One dense adjacency per (graph identity, dtype): id(weights) is stable
# because RoadNetwork.csr() caches its arrays.
_DENSE_CACHE: Dict[Tuple[int, str], Tuple[weakref.ref, object]] = {}


def _dense_w(indptr: np.ndarray, indices: np.ndarray, weights: np.ndarray, dtype):
    from .spotlight_ball.ref import dense_adjacency

    key = (id(weights), np.dtype(dtype).str)
    entry = _DENSE_CACHE.get(key)
    if entry is not None and entry[0]() is weights:
        _STATS["device_cache_hits"] += 1
        return entry[1]
    # (the _device_resident call below accounts for the cache miss)
    W_host = dense_adjacency(
        np.asarray(indptr), np.asarray(indices), np.asarray(weights, dtype=dtype)
    )
    dev = _device_resident(W_host)

    def _evict(_ref, key=key):
        _DENSE_CACHE.pop(key, None)

    _DENSE_CACHE[key] = (weakref.ref(weights, _evict), dev)
    return dev


# --------------------------------------------------------------------- #
# Batched spotlight balls                                                #
# --------------------------------------------------------------------- #
def _make_ball_padded():
    import jax
    import jax.numpy as jnp

    from .spotlight_ball.ref import relax_step_ref

    # Donating the per-call scratch operands lets the backend alias their
    # buffers; CPU does not implement donation and would warn on every
    # compile, so only donate where it is real.
    donate = (1, 2) if jax.default_backend() == "tpu" else ()

    @functools.partial(
        jax.jit,
        static_argnames=("use_pallas", "interpret"),
        donate_argnums=donate,
    )
    def ball_padded(W, sources, radii, *, use_pallas: bool, interpret: bool):
        V = W.shape[0]
        Q = sources.shape[0]
        inf = jnp.array(jnp.inf, dtype=W.dtype)
        D0 = jnp.full((Q, V), inf, dtype=W.dtype)
        D0 = D0.at[jnp.arange(Q), sources].set(jnp.zeros((), dtype=W.dtype))

        if use_pallas:
            from .spotlight_ball.kernel import relax_step_pallas

            step = lambda D: relax_step_pallas(D, W, interpret=interpret)
        else:
            step = lambda D: relax_step_ref(D, W)

        def cond(state):
            D, changed, it = state
            return jnp.logical_and(changed, it < V)

        def body(state):
            D, _, it = state
            Dn = step(D)
            return Dn, jnp.any(Dn < D), it + 1

        D, _, _ = jax.lax.while_loop(cond, body, (D0, jnp.bool_(True), jnp.int32(0)))
        return jnp.where(D <= radii[:, None], D, inf)

    return ball_padded


_BALL_PADDED = None


def spotlight_ball(indptr, indices, weights, sources, radii, *, dtype=np.float32):
    """Bucket-padded batched Dijkstra balls over a CSR graph.

    Same contract as ``repro.kernels.spotlight_ball.ops.spotlight_ball``
    (returns (Q, V) distances, ``inf`` outside each radius) but the dense
    adjacency is device-resident per graph, and Q is padded to a
    power-of-two bucket (pad queries get radius ``-1`` and therefore
    all-``inf`` rows, which are sliced off).  Rows are independent under
    min-plus relaxation, so real rows are bitwise identical to an
    unpadded call.
    """
    global _BALL_PADDED
    import jax
    import jax.numpy as jnp

    _STATS["ball_calls"] += 1
    sources = np.asarray(sources, dtype=np.int32)
    Q = sources.shape[0]
    qb = bucket(Q)
    src_pad = np.zeros(qb, dtype=np.int32)
    src_pad[:Q] = sources
    rad_pad = np.full(qb, -1.0, dtype=dtype)
    rad_pad[:Q] = np.asarray(radii, dtype=dtype)

    W = _dense_w(indptr, indices, weights, dtype)
    use_pallas = _use_pallas()
    interpret = pallas_interpret()
    if _BALL_PADDED is None:
        _BALL_PADDED = _make_ball_padded()
    key = ("ball", int(W.shape[0]), qb, np.dtype(dtype).str, use_pallas)
    _note_shape(key)
    bound_jit_cache("ball", _BALL_PADDED, key)
    out = _BALL_PADDED(
        W,
        jnp.asarray(src_pad),
        jnp.asarray(rad_pad),
        use_pallas=use_pallas,
        interpret=interpret,
    )
    return out[:Q]


# --------------------------------------------------------------------- #
# Batched re-id matching                                                 #
# --------------------------------------------------------------------- #
def _make_reid_padded():
    import jax
    import jax.numpy as jnp

    donate = (0,) if jax.default_backend() == "tpu" else ()

    # threshold is traced (not static): sweeps vary it per config, and a
    # static arg would recompile per distinct value — violating the
    # one-compile-per-bucket-shape contract without showing up in stats.
    @functools.partial(jax.jit, donate_argnums=donate)
    def reid_padded(gallery, queries, nq, threshold):
        # Same arithmetic as reid_match_ref, with pad queries masked to
        # -inf similarity so they can never win the per-candidate max.
        g = gallery.astype(jnp.float32)
        q = queries.astype(jnp.float32)
        g = g / jnp.maximum(jnp.linalg.norm(g, axis=-1, keepdims=True), 1e-6)
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)
        # HIGHEST: a TPU runs a default-precision f32 matmul as bf16 passes.
        sim = jnp.matmul(g, q.T, precision=jax.lax.Precision.HIGHEST)  # (N, Qb)
        valid = jnp.arange(q.shape[0])[None, :] < nq
        sim = jnp.where(valid, sim, -jnp.inf)
        scores = jnp.max(sim, axis=-1)
        best = jnp.argmax(sim, axis=-1).astype(jnp.int32)
        return scores, best, scores >= threshold

    return reid_padded


_REID_PADDED = None


def reid_match(gallery, queries, *, threshold: float = 0.5):
    """Bucket-padded re-id matcher: ``(scores, best_query, is_match)`` for
    the first ``N`` gallery rows, matching the unpadded
    ``repro.kernels.reid_match`` call up to last-ulp GEMM reassociation
    (padding changes the matmul blocking; results are deterministic per
    shape).

    The gallery (per-call candidate embeddings) is padded to a
    power-of-two row bucket and donated; the query block (often a
    long-lived entity embedding) is padded once and kept device-resident
    keyed on its identity.
    """
    global _REID_PADDED
    import jax.numpy as jnp

    _STATS["reid_calls"] += 1
    gallery = np.asarray(gallery, dtype=np.float32)
    if gallery.ndim != 2:
        raise ValueError(f"gallery must be (N, D), got {gallery.shape}")
    N, D = gallery.shape
    nb = bucket(N)
    g_pad = np.zeros((nb, D), dtype=np.float32)
    g_pad[:N] = gallery

    queries_np = np.asarray(queries, dtype=np.float32)
    if queries_np.ndim != 2 or queries_np.shape[1] != D:
        raise ValueError(f"queries must be (Q, {D}), got {queries_np.shape}")
    Q = queries_np.shape[0]
    qb = bucket(Q)

    def _pad_queries(_q):
        q_pad = np.zeros((qb, D), dtype=np.float32)
        q_pad[:Q] = queries_np
        return q_pad

    if isinstance(queries, np.ndarray):
        # Long-lived query blocks (the tracked entity's embedding) stay
        # device-resident, padded once, keyed on the host array identity.
        q_dev = _device_resident(queries, transform=_pad_queries)
    else:
        q_dev = jnp.asarray(_pad_queries(queries_np))

    if _REID_PADDED is None:
        _REID_PADDED = _make_reid_padded()
    key = ("reid", nb, qb, D)
    _note_shape(key)
    bound_jit_cache("reid", _REID_PADDED, key)
    scores, best, matched = _REID_PADDED(
        jnp.asarray(g_pad), q_dev, jnp.int32(Q), jnp.float32(threshold)
    )
    return scores[:N], best[:N], matched[:N]


# --------------------------------------------------------------------- #
# Query-major batched re-id (multi-query tenancy plane)                   #
# --------------------------------------------------------------------- #
#: Gallery rows of the multi matcher's smallest launch, and the rows one
#: query block may queue: a flush answers up to this many queued rows in one
#: launch of one program per query bucket.  A call of more rows launches
#: alone, in ``bucket(N)`` rows.
REID_MULTI_ROWS = 64


def _make_reid_multi_padded():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reid_multi_padded(gallery, queries, mask):
        # Per-(candidate, query) cosine similarity with a broadcast
        # multiply-then-reduce over the feature axis: every sim[n, q] is an
        # independent D-length reduction whose arithmetic does not depend on
        # how many other rows/queries share the bucket — which is what makes
        # the fused call bit-exact against per-query serial dispatches, and
        # a flush's stacked rows bit-exact against one launch a call.
        g = gallery.astype(jnp.float32)
        q = queries.astype(jnp.float32)
        g = g / jnp.maximum(jnp.linalg.norm(g, axis=-1, keepdims=True), 1e-6)
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)
        sim = jnp.sum(g[:, None, :] * q[None, :, :], axis=-1)  # (Nb, Qb)
        return jnp.where(mask, sim, -jnp.inf)

    return reid_multi_padded


_REID_MULTI_PADDED = None


class ReidAnswer:
    """One half of a :func:`reid_match_multi` answer.  ``np.asarray`` reads
    it as a read-only host ``(N, Q)`` array; reading an answer whose call
    is still queued first answers every queued call (:func:`reid_flush`)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None):
        if self.value is None:
            reid_flush()
            if self.value is None:
                raise RuntimeError("re-ID answer lost: the launch that held it failed")
        v = self.value
        if dtype is not None and np.dtype(dtype) != v.dtype:
            return v.astype(dtype)
        return v.copy() if copy else v


class _Block:
    """The queued calls against one query block, in call order."""

    __slots__ = ("queries", "q_dev", "qb", "rows", "calls")

    def __init__(self, queries, q_dev, qb: int) -> None:
        self.queries = queries  # held, so its id names this block while queued
        self.q_dev = q_dev
        self.qb = qb
        self.rows = 0
        #: (gallery, mask, threshold, scores, matched) per call
        self.calls: list = []


# Query-block key -> its queued calls, blocks in the order of their first call.
_QUEUE: Dict[object, _Block] = {}


def reid_match_multi(gallery, queries, *, mask=None, threshold: float = 0.5):
    """Query-major batched re-id: ``(scores, matched)`` of shape ``(N, Q)``
    for an ``(N, D)`` gallery against ``(Q, D)`` query embeddings.

    ``mask`` (optional ``(N, Q)`` bool) is the tenancy filter: pair
    ``(n, q)`` is only evaluated when ``mask[n, q]`` — masked-out pairs get
    ``-inf`` score and ``matched=False``.  Both axes are padded to
    power-of-two buckets (pad pairs masked out; rows to at least
    :data:`REID_MULTI_ROWS`), so a whole multi-query sweep compiles this
    kernel at most once per bucket shape.

    The call validates its operands and queues them; it launches nothing.
    The answers are :class:`ReidAnswer` handles: ``np.asarray`` reads each
    as a read-only host array (float32 scores, bool flags).  The first read
    of any queued answer answers the whole queue with one device launch per
    query block, the block's calls' rows stacked in call order, and cuts
    each call's answer out on the host; ``matched`` is ``mask & (scores >=
    float32(threshold))`` there.

    Bit-exactness contract: each ``sim[n, q]`` is an independent
    normalize-then-reduce over ``D``, so real entries are **bitwise** equal
    to a per-query serial call (``Q=1``) with the same gallery rows, and to
    the same call launched alone — unlike :func:`reid_match`, no GEMM
    re-blocking is involved.  The fused multi-query VA stage relies on this
    to stay bit-identical to N independent single-query runs.
    """
    with span("repro.reid.dispatch"):
        return _reid_enqueue(gallery, queries, mask, threshold)


def _reid_enqueue(gallery, queries, mask, threshold):
    with span("repro.reid.prep"):
        _STATS["reid_multi_calls"] += 1
        # Copies: the queue outlives the caller's buffers.
        gallery = np.array(gallery, dtype=np.float32)
        if gallery.ndim != 2:
            raise ValueError(f"gallery must be (N, D), got {gallery.shape}")
        N, D = gallery.shape
        queries_np = np.asarray(queries, dtype=np.float32)
        if queries_np.ndim != 2 or queries_np.shape[1] != D:
            raise ValueError(f"queries must be (Q, {D}), got {queries_np.shape}")
        Q = queries_np.shape[0]
        if mask is None:
            mask_np = np.ones((N, Q), dtype=bool)
        else:
            mask_np = np.array(mask, dtype=bool)
            if mask_np.shape != (N, Q):
                raise ValueError(f"mask must be ({N}, {Q}), got {mask_np.shape}")
        qb = bucket(Q)

        def _pad_queries(_q):
            q_pad = np.zeros((qb, D), dtype=np.float32)
            q_pad[:Q] = queries_np
            return q_pad

        if isinstance(queries, np.ndarray):
            # The live-query block is long-lived (the query registry caches
            # one array per live set): pad once, keep device-resident by
            # identity — same contract as the single-query reid_match block.
            key = id(queries)
            q_dev = _device_resident(queries, transform=_pad_queries)
        else:
            key = object()  # a block of its own
            q_dev = _pad_queries(queries_np)
        block = _QUEUE.get(key)
    if block is not None and block.rows + N > REID_MULTI_ROWS:
        reid_flush()
        block = None
    if block is None:
        block = _QUEUE[key] = _Block(queries, q_dev, qb)
    scores, matched = ReidAnswer(), ReidAnswer()
    block.calls.append((gallery, mask_np, np.float32(threshold), scores, matched))
    block.rows += N
    return scores, matched


def reid_queued() -> int:
    """Calls of :func:`reid_match_multi` not yet answered."""
    return sum(len(b.calls) for b in _QUEUE.values())


def reid_flush() -> None:
    """Answer every queued :func:`reid_match_multi` call: one launch of the
    matcher per query block, the block's rows stacked in call order."""
    if not _QUEUE:
        return
    blocks = list(_QUEUE.values())
    _QUEUE.clear()
    with span("repro.reid.flush"):
        for block in blocks:
            _reid_launch(block)


def _reid_launch(block: _Block) -> None:
    global _REID_MULTI_PADDED

    D = block.calls[0][0].shape[1]
    nb = bucket(block.rows, REID_MULTI_ROWS)
    g_pad = np.zeros((nb, D), dtype=np.float32)
    m_pad = np.zeros((nb, block.qb), dtype=bool)
    r = 0
    for g, m, _, _, _ in block.calls:
        g_pad[r:r + len(g)] = g
        m_pad[r:r + len(g), :m.shape[1]] = m
        r += len(g)
    if _REID_MULTI_PADDED is None:
        _REID_MULTI_PADDED = _make_reid_multi_padded()
    key = ("reid_multi", nb, block.qb, D)
    _note_shape(key)
    bound_jit_cache("reid_multi", _REID_MULTI_PADDED, key)
    _STATS["reid_multi_launches"] += 1
    with span("repro.reid.call"):
        sim = _REID_MULTI_PADDED(g_pad, block.q_dev, m_pad)
    with span("repro.reid.slice"):
        sim = np.asarray(sim)
        sim.flags.writeable = False
        r = 0
        for g, m, thr, scores, matched in block.calls:
            s = sim[r:r + len(g), :m.shape[1]]
            # The kernel's own float32 comparison, made on the host.
            flags = m & (s >= thr)
            flags.flags.writeable = False
            scores.value, matched.value = s, flags
            r += len(g)


def jit_cache_sizes() -> Dict[str, int]:
    """Number of distinct compilations held by each padded kernel (0 when
    the kernel has not been dispatched yet)."""
    # the mega-step scan shares the bounded-jit-cache contract
    from .megastep import ops as _mega_ops

    return {
        name: 0 if fn is None else fn._cache_size()
        for name, fn in (
            ("ball", _BALL_PADDED),
            ("reid", _REID_PADDED),
            ("reid_multi", _REID_MULTI_PADDED),
            ("megastep", _mega_ops._CHUNK_FN),
        )
    }
