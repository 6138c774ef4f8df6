"""Device mega-step: the whole drops-off run as K-tick ``lax.scan`` chunks.

The engine keeps everything the hot loop touches resident on device —
camera activity masks (the per-query ``applied`` bit matrix), the query tag
bits packed into one uint64 per camera, the visibility table, the spotlight
distance/hop planes, the radius/hop tables and the shared CR verdict
stream — and executes frames -> VA -> CR -> sink rows -> TL spotlight ->
control update for all queries and K ticks per dispatch.  Only compact
per-(tick, lane, slot) summary rows come back to the host, which rebuilds
``ref.SinkRow`` records and the per-query books from them.

Bit-exactness: every float op is an f64 add/sub/compare in the exact order
of the numpy reference (no multiplies anywhere on the device path, so no
FMA contraction; tables carrying the radius arithmetic are host-built), so
rows are bit-identical to ``ref.run_chain`` + ``ref.make_table_tl`` —
where the backend's f64 is IEEE binary64 (:func:`x64_exact`).  A TPU
lowers f64 to f32 pairs; there the scan does not run and the caller takes
the host reference, reason ``x64-emulated``.

Shapes are bucket-padded (cameras, queries, lane slots, detection ring,
ticks-per-chunk, table dims) so a sweep compiles the scan at most once per
bucket shape; the compile cache is bounded through
``dispatch.bound_jit_cache`` like every other padded kernel.  Data-driven
capacities (slots per lane, in-flight detections) carry sticky overflow
flags: on overflow the run is retried with the offending dimension
doubled, and past the caps the caller falls back to the host reference.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import dispatch
from . import ref as _ref

__all__ = ["run_chain_device", "last_xfer_seconds", "last_chunk_seconds",
           "last_fallback_reason", "x64_exact", "lane_chain_uses_pallas",
           "KMAX", "RING_CAP"]

KMAX = 256        # ticks per dispatch (chunk) cap
RING_CAP = 1 << 14  # in-flight detection records before host fallback

_CHUNK_FN = None

# Device->host transfer wall of the most recent run_chain_device call (the
# per-chunk summary pulls + the final carry).  Benchmarks report this as
# the separate ``xfer_s`` column so compute and transfer don't blur.
_LAST_XFER_S = 0.0
_LAST_FALLBACK = ""
# Per backend: does f64 add/sub/compare there round like IEEE binary64?
# Bit-identity with the host reference rests on it (module docstring).
_X64_EXACT: Dict[str, bool] = {}
# Per-chunk host wall (dispatch + device compute + summary pull) of the most
# recent run_chain_device call — the observability plane's mega-step profile
# (repro.obs.collect_engine).  Attribution only, never a decision input.
_CHUNK_WALL_S: list = []


def last_xfer_seconds() -> float:
    return _LAST_XFER_S


def last_chunk_seconds() -> list:
    """Per-chunk wall times (seconds) of the most recent
    :func:`run_chain_device` call, in chunk order; empty when the device
    path was never tried or fell back before the scan."""
    return list(_CHUNK_WALL_S)


def last_fallback_reason() -> str:
    """Why the most recent :func:`run_chain_device` call returned None:
    ``"x64-emulated"``, ``"queries>64"``, ``"device-capacity"`` or
    ``"device-error: <repr>"`` ("" when the scan ran or was never tried).
    The catch behind the last one is broad on purpose — *any* backend
    failure must fall back, exactness preserved — but never silently."""
    return _LAST_FALLBACK


def x64_exact() -> bool:
    """Whether the default backend adds, subtracts and compares f64 exactly
    as IEEE binary64 does, round trip to the host included.  A TPU lowers
    f64 to pairs of f32, which keeps about 48 mantissa bits and f32's
    exponent range, so its sums differ from the host reference's in the
    last bits.  Probed once per backend on seeded operands that use the
    full 53-bit mantissa."""
    import jax

    backend = jax.default_backend()
    ok = _X64_EXACT.get(backend)
    if ok is None:
        rng = np.random.default_rng(0)
        a = rng.uniform(-1e3, 1e3, 1024)
        b = rng.uniform(-1e3, 1e3, 1024)
        with jax.enable_x64(True):
            s, d, lt = jax.device_get(
                jax.jit(lambda x, y: (x + y, x - y, x + y < y))(a, b)
            )
        ok = bool(
            np.array_equal(s, a + b)
            and np.array_equal(d, a - b)
            and np.array_equal(lt, a + b < b)
        )
        _X64_EXACT[backend] = ok
    return ok


def lane_chain_uses_pallas(dtype) -> bool:
    """Whether the chain sweep runs as the Pallas lane-chain kernel for
    chain state of ``dtype``.  Mosaic refuses 64-bit operands in a Pallas
    call, so f64 state keeps the jnp slot scan on every platform."""
    return dispatch._use_pallas() and np.dtype(dtype).itemsize <= 4


def _build_chunk_fn():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chunk(carry, ftimes_k, valid_k, vis_k, k0, scalars, tables,
              *, use_pallas: bool, interpret: bool):
        xi_fc, xi_va, xi_cr, d_fv, d_vc, d_cu, p_tp = scalars
        (lane_of, uniforms, modes, rgroup, r_tab, h_tab,
         cand_of_cam, dist_plane, hop_plane, qvalid, cvalid, slot_iota) = tables

        Nb, Cb = carry[0].shape
        L = carry[3].shape[0]
        S = slot_iota.shape[0]
        R = carry[8].shape[0]
        Tb = r_tab.shape[-1]
        U = uniforms.shape[0]
        INT_BIG = jnp.iinfo(jnp.int64).max

        lane_ids = jnp.arange(L, dtype=jnp.int64)
        cam_ids = jnp.arange(Cb, dtype=jnp.int64)
        q_shift = jnp.arange(Nb, dtype=jnp.uint64)
        lane_onehot = lane_of[:, None] == lane_ids[None, :]      # (Cb, L)

        def tick_step(c, xs):
            (applied, ls_cam, ls_tick, va_b, va_armed, cr_b, cr_armed, draws,
             ring_valid, ring_auv, ring_tick, ring_gen, ring_cam, ring_pos,
             ring_mask, of_slots, of_ring) = c
            now, valid, vis_row, i = xs
            k = k0 + i

            # ---- TL tick (fires before the frame tick for k >= 1 and
            # consumes detections that arrived strictly before it) -------- #
            do_tl = valid & (k >= 1)
            take = ring_valid & (ring_auv < now) & do_tl          # (R,)
            cand = take[:, None] & ring_mask & ring_pos[:, None]  # (R, Nb)
            any_pos = cand.any(axis=0)
            # Newest positive per query: max source tick, then first in
            # sink order (min a_uv, then min generation index).
            tickv = jnp.where(cand, ring_tick[:, None], jnp.int64(-1))
            best_tick = tickv.max(axis=0)
            cand2 = cand & (ring_tick[:, None] == best_tick[None, :])
            auvv = jnp.where(cand2, ring_auv[:, None], jnp.inf)
            best_auv = auvv.min(axis=0)
            cand3 = cand2 & (ring_auv[:, None] == best_auv[None, :])
            genv = jnp.where(cand3, ring_gen[:, None], INT_BIG)
            win = jnp.argmin(genv, axis=0)                        # (Nb,)
            upd = do_tl & any_pos
            ls_cam = jnp.where(upd, ring_cam[win], ls_cam)
            ls_tick = jnp.where(upd, best_tick, ls_tick)
            ring_valid = ring_valid & ~take

            # Spotlight from the table planes: pure gathers + compares.
            kt = jnp.minimum(k, Tb - 1)
            lst = jnp.minimum(ls_tick, Tb - 1)
            src = jnp.maximum(cand_of_cam[ls_cam], 0)
            hops = h_tab[rgroup, lst, kt]                         # (Nb,)
            rad = r_tab[rgroup, lst, kt]
            req_hot = cam_ids[None, :] == ls_cam[:, None]
            req_bfs = hop_plane[src] <= hops[:, None]
            req_wbfs = dist_plane[src] <= rad[:, None]
            req = jnp.where(
                (modes == 0)[:, None], True,
                jnp.where(any_pos[:, None], req_hot,
                          jnp.where((modes == 1)[:, None], req_bfs, req_wbfs)),
            )
            req = req & qvalid[:, None] & cvalid[None, :]
            new_req = jnp.where(do_tl, req, applied)
            tl_counts = jnp.where(do_tl, new_req.sum(axis=1, dtype=jnp.int64), 0)
            tl_union = jnp.where(
                do_tl, new_req.any(axis=0).sum(dtype=jnp.int64), 0
            )

            # ---- sourcing: uses the PREVIOUS tick's applied matrix (the
            # TL's control deltas land one control latency later) --------- #
            bits = jnp.sum(
                jnp.where(applied, jnp.uint64(1) << q_shift[:, None],
                          jnp.uint64(0)),
                axis=0, dtype=jnp.uint64,
            )                                                     # (Cb,)
            active = applied.any(axis=0) & valid                  # (Cb,)
            act_lane = active[:, None] & lane_onehot              # (Cb, L)
            cum = jnp.cumsum(act_lane.astype(jnp.int64), axis=0)
            slot = jnp.take_along_axis(cum, lane_of[:, None], axis=1)[:, 0] - 1
            n_l = cum[-1]                                         # (L,)
            of_slots = of_slots | (n_l.max() > S)
            camv = jnp.where(act_lane, cam_ids[:, None], INT_BIG)
            min_cam = camv.min(axis=0)                            # (L,)
            grank = jnp.sum(
                min_cam[None, :] < min_cam[:, None], axis=1, dtype=jnp.int64
            )

            ok = active & (slot < S)
            scat = jnp.where(ok, lane_of * S + slot, L * S)
            cam_at = jnp.full(L * S, -1, dtype=jnp.int64).at[scat].set(
                cam_ids, mode="drop"
            ).reshape(L, S)
            real_ls = cam_at >= 0
            cam_c = jnp.maximum(cam_at, 0)
            has_ls = vis_row[cam_c] & real_ls

            t_arr = (now + xi_fc) + d_fv

            if use_pallas:
                from .kernel import lane_chain_tick_pallas

                params = jnp.stack([t_arr, xi_va, xi_cr, d_vc, d_cu, p_tp])
                (va_b, va_armed, cr_b, cr_armed, draws,
                 va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos) = (
                    lane_chain_tick_pallas(
                        real_ls, has_ls, va_b, va_armed, cr_b, cr_armed,
                        draws, uniforms, params, interpret=interpret,
                    )
                )
            else:
                def slot_step(cc, s):
                    b_v, a_v, b_c, a_c, dr = cc
                    real = real_ls[:, s]
                    has = has_ls[:, s]
                    fu_v = t_arr >= b_v
                    st_v = jnp.where(a_v, b_v, t_arr + (b_v - t_arr))
                    end_v = jnp.where(fu_v, t_arr + xi_va, st_v + xi_va)
                    q_v = jnp.where(fu_v, 0.0, st_v - t_arr)
                    b_v = jnp.where(real, end_v, b_v)
                    a_v = jnp.where(real, ~fu_v, a_v)
                    arr_c = end_v + d_vc
                    fu_c = arr_c >= b_c
                    st_c = jnp.where(a_c, b_c, arr_c + (b_c - arr_c))
                    end_c = jnp.where(fu_c, arr_c + xi_cr, st_c + xi_cr)
                    q_c = jnp.where(fu_c, 0.0, st_c - arr_c)
                    b_c = jnp.where(real, end_c, b_c)
                    a_c = jnp.where(real, ~fu_c, a_c)
                    u = uniforms[jnp.minimum(dr, U - 1)]
                    drawn = real & has
                    p = drawn & (u <= p_tp)
                    dr = dr + drawn
                    return (b_v, a_v, b_c, a_c, dr), (
                        end_v, q_v, fu_v, end_c, q_c, fu_c, end_c + d_cu, p
                    )

                (va_b, va_armed, cr_b, cr_armed, draws), so = lax.scan(
                    slot_step, (va_b, va_armed, cr_b, cr_armed, draws),
                    slot_iota,
                )
                (va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos) = (
                    x.T for x in so
                )

            # ---- detection ring insertion ------------------------------- #
            real_flat = real_ls.reshape(-1)
            gen_flat = (
                (k * L + grank[:, None]) * S + slot_iota[None, :]
            ).reshape(-1)
            cam_flat = cam_c.reshape(-1)
            mask_flat = applied.T[cam_flat]                        # (L*S, Nb)
            free = ~ring_valid
            n_free = free.sum(dtype=jnp.int64)
            n_new = real_flat.sum(dtype=jnp.int64)
            of_ring = of_ring | (n_new > n_free)
            frank = jnp.cumsum(free.astype(jnp.int64)) - 1
            slot_of_rank = jnp.full(R, R, dtype=jnp.int64).at[
                jnp.where(free, frank, R)
            ].set(jnp.arange(R, dtype=jnp.int64), mode="drop")
            erank = jnp.cumsum(real_flat.astype(jnp.int64)) - 1
            dest = jnp.where(
                real_flat, slot_of_rank[jnp.minimum(erank, R - 1)], R
            )
            ring_valid = ring_valid.at[dest].set(True, mode="drop")
            ring_auv = ring_auv.at[dest].set(a_uv.reshape(-1), mode="drop")
            ring_tick = ring_tick.at[dest].set(k, mode="drop")
            ring_gen = ring_gen.at[dest].set(gen_flat, mode="drop")
            ring_cam = ring_cam.at[dest].set(cam_flat, mode="drop")
            ring_pos = ring_pos.at[dest].set(pos.reshape(-1), mode="drop")
            ring_mask = ring_mask.at[dest].set(mask_flat, mode="drop")

            c2 = (new_req, ls_cam, ls_tick, va_b, va_armed, cr_b, cr_armed,
                  draws, ring_valid, ring_auv, ring_tick, ring_gen, ring_cam,
                  ring_pos, ring_mask, of_slots, of_ring)
            ys = (bits, tl_counts, tl_union, grank, cam_at, real_ls,
                  va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos)
            return c2, ys

        K = ftimes_k.shape[0]
        xs = (ftimes_k, valid_k, vis_k, jnp.arange(K, dtype=jnp.int64))
        return lax.scan(tick_step, carry, xs)

    return jax.jit(chunk, static_argnames=("use_pallas", "interpret"))


def _plan_device_tables(plan, jnp, Nb, Cb, Tb):
    """Pad the host-built plan tables to bucket shapes and upload."""
    C = plan.num_cameras
    N = len(plan.modes)
    T = len(plan.ftimes)
    i64max = np.iinfo(np.int64).max

    G = max(len(plan.r_tabs), 1)
    Gb = dispatch.bucket(G)
    r_tab = np.zeros((Gb, Tb, Tb), dtype=np.float64)
    h_tab = np.zeros((Gb, Tb, Tb), dtype=np.int64)
    for g in range(len(plan.r_tabs)):
        r_tab[g, :T, :T] = plan.r_tabs[g]
        h_tab[g, :T, :T] = plan.h_tabs[g]

    ncand = max(plan.dist_plane.shape[0], 1)
    NCb = dispatch.bucket(ncand)
    dist = np.full((NCb, Cb), np.inf)
    hop = np.full((NCb, Cb), i64max, dtype=np.int64)
    nc = plan.dist_plane.shape[0]
    dist[:nc, :C] = plan.dist_plane
    hop[:nc, :C] = plan.hop_plane

    cand_of_cam = np.zeros(Cb, dtype=np.int64)
    cand_of_cam[:C] = plan.cand_of_cam
    lane_of = np.zeros(Cb, dtype=np.int64)
    lane_of[:C] = plan.lane_of
    modes = np.ones(Nb, dtype=np.int8)
    modes[:N] = plan.modes
    rgroup = np.zeros(Nb, dtype=np.int64)
    rgroup[:N] = plan.rgroup
    U = dispatch.bucket(max(len(plan.uniforms), 1))
    uniforms = np.full(U, 2.0)  # pad draws can never read as positive
    uniforms[: len(plan.uniforms)] = plan.uniforms
    qvalid = np.arange(Nb) < N
    cvalid = np.arange(Cb) < C
    return (
        jnp.asarray(lane_of), jnp.asarray(uniforms),
        jnp.asarray(modes), jnp.asarray(rgroup),
        jnp.asarray(r_tab), jnp.asarray(h_tab),
        jnp.asarray(cand_of_cam), jnp.asarray(dist), jnp.asarray(hop),
        jnp.asarray(qvalid), jnp.asarray(cvalid),
    ), (Gb, NCb, U)


def _initial_capacities(plan, seed_applied) -> Tuple[int, int, int]:
    L = plan.num_lanes
    C = plan.num_cameras
    union = seed_applied.any(axis=0)
    s0 = 0
    if union.any():
        s0 = int(np.bincount(plan.lane_of[union], minlength=L).max())
    s_max = dispatch.bucket(max(int(math.ceil(C / max(L, 1))), 1))
    S = min(dispatch.bucket(max(4, s0)), s_max)
    R = min(dispatch.bucket(max(64, 4 * L * S)), RING_CAP)
    return S, R, s_max


def _assemble(plan, seed_applied, ys, final_applied, d_vc, d_cu,
              counters=None):
    """Rebuild the ChainOutput (rows in final sink order, per-query books)
    from the device scan's per-tick summaries — every float reconstructed
    here is a single IEEE add of the same operands the reference uses.

    ``counters=(sourced, query_positives)`` skips the host-side per-query
    recount: the sharded engine all-reduces these on device (one psum per
    chunk) and hands the exact integer books over directly."""
    (bits, tlc, tlu, grank, cam_at, real,
     va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos) = ys
    T = len(plan.ftimes)
    N = seed_applied.shape[0]
    C = plan.num_cameras
    ftimes = plan.ftimes
    horizon = plan.horizon

    ts, ls_, ss = np.nonzero(real)
    cam_e = cam_at[ts, ls_, ss]
    gr_e = grank[ts, ls_]
    bits_rows = bits[ts, cam_e]
    masks = (
        (bits_rows[:, None] >> np.arange(N, dtype=np.uint64)[None, :])
        & np.uint64(1)
    ).astype(bool)
    vend_e = va_end[ts, ls_, ss]
    qva_e = q_va[ts, ls_, ss]
    vafu_e = va_fu[ts, ls_, ss]
    cend_e = cr_end[ts, ls_, ss]
    qcr_e = q_cr[ts, ls_, ss]
    crfu_e = cr_fu[ts, ls_, ss]
    auv_e = a_uv[ts, ls_, ss]
    pos_e = pos[ts, ls_, ss]

    # Rows come in (tick, lane, slot) order: each lane's chain order.
    va_line = [_ref.QueueLineage() for _ in range(plan.num_lanes)]
    cr_line = [_ref.QueueLineage() for _ in range(plan.num_lanes)]
    rows: List[_ref.SinkRow] = []
    for e in range(len(ts)):
        t = int(ts[e])
        lane = int(ls_[e])
        grank, slot = int(gr_e[e]), int(ss[e])
        now = float(ftimes[t])
        a = float(auv_e[e])
        vend = float(vend_e[e])
        va_fused, cr_fused = bool(vafu_e[e]), bool(crfu_e[e])
        rows.append(_ref.SinkRow(
            a_uv=a, tick=t, grank=grank, slot=slot,
            lane=lane, cam=int(cam_e[e]), positive=bool(pos_e[e]),
            u=a - now, q_bar=(0.0 + float(qva_e[e])) + float(qcr_e[e]),
            va_fused=va_fused, va_end=vend, cr_arr=vend + d_vc,
            cr_fused=cr_fused, cr_end=float(cend_e[e]),
            mask=masks[e],
            order=_ref.sink_order(va_line[lane], cr_line[lane], t, grank,
                                  slot, va_fused, cr_fused, vend + d_vc),
        ))
    rows.sort(key=_ref.sink_sort_key)

    union_rows = bits[:, :C] != 0
    g_source = int(union_rows.sum())
    g_pos = int((union_rows & plan.vis).sum())
    if counters is not None:
        sourced = np.asarray(counters[0], dtype=np.int64)
        qpos = np.asarray(counters[1], dtype=np.int64)
    else:
        sourced = np.zeros(N, dtype=np.int64)
        qpos = np.zeros(N, dtype=np.int64)
        for q in range(N):
            m = ((bits[:, :C] >> np.uint64(q)) & np.uint64(1)).astype(bool)
            sourced[q] = m.sum()
            qpos[q] = (m & plan.vis).sum()

    tl_counts = [
        (k, tlc[k, :N].astype(np.int64), int(tlu[k])) for k in range(1, T)
    ]

    L = plan.num_lanes
    va_execs = np.zeros(L, dtype=np.int64)
    cr_execs = np.zeros(L, dtype=np.int64)
    for r in rows:
        if r.va_fused or r.va_end <= horizon:
            va_execs[r.lane] += 1
        if r.cr_arr <= horizon and (r.cr_fused or r.cr_end <= horizon):
            cr_execs[r.lane] += 1

    return _ref.ChainOutput(
        rows=rows,
        source_events=g_source,
        positives_generated=g_pos,
        sourced=sourced,
        query_positives=qpos,
        tl_counts=tl_counts,
        va_exec_counts=va_execs,
        cr_exec_counts=cr_execs,
        final_req=np.ascontiguousarray(final_applied[:N, :C]),
    )


def run_chain_device(plan, seed_applied) -> Optional[_ref.ChainOutput]:
    """Run the fused scan on device; None means "use the host reference",
    with the reason in :func:`last_fallback_reason`."""
    global _CHUNK_FN, _LAST_XFER_S, _LAST_FALLBACK
    import jax
    import jax.numpy as jnp

    _LAST_XFER_S = 0.0
    _LAST_FALLBACK = ""
    del _CHUNK_WALL_S[:]
    N = seed_applied.shape[0]
    Nb = min(dispatch.bucket(N), 64)
    if N > Nb:
        _LAST_FALLBACK = "queries>64"
        return None

    try:
        if not x64_exact():
            _LAST_FALLBACK = "x64-emulated"
            return None
        with jax.enable_x64(True):
            if _CHUNK_FN is None:
                _CHUNK_FN = _build_chunk_fn()
            fn = _CHUNK_FN

            C = plan.num_cameras
            L = plan.num_lanes
            T = len(plan.ftimes)
            Cb = dispatch.bucket(C)
            Tb = dispatch.bucket(T)
            K = min(dispatch.bucket(T), KMAX)
            nchunk = (T + K - 1) // K

            tables_np, (Gb, NCb, U) = _plan_device_tables(plan, jnp, Nb, Cb, Tb)
            use_pallas = lane_chain_uses_pallas(np.float64)
            interpret = dispatch.pallas_interpret()
            scalars = tuple(
                jnp.asarray(v, jnp.float64)
                for v in (plan.xi_fc, plan.xi_va, plan.xi_cr,
                          plan.d_fv, plan.d_vc, plan.d_cu, plan.p_tp)
            )
            vis_pad = np.zeros((nchunk * K, Cb), dtype=bool)
            vis_pad[:T, :C] = plan.vis
            ft_pad = np.full(nchunk * K, float(plan.ftimes[-1]))
            ft_pad[:T] = plan.ftimes
            valid_pad = np.arange(nchunk * K) < T

            applied0 = np.zeros((Nb, Cb), dtype=bool)
            applied0[:N, :C] = seed_applied
            ls_cam0 = np.zeros(Nb, dtype=np.int64)
            ls_cam0[:N] = plan.seed_ls_cam

            S, R, s_max = _initial_capacities(plan, seed_applied)
            while True:
                tables = tables_np + (jnp.arange(S, dtype=jnp.int64),)
                carry = (
                    jnp.asarray(applied0),
                    jnp.asarray(ls_cam0),
                    jnp.zeros(Nb, dtype=jnp.int64),
                    jnp.full(L, -jnp.inf, dtype=jnp.float64),
                    jnp.zeros(L, dtype=bool),
                    jnp.full(L, -jnp.inf, dtype=jnp.float64),
                    jnp.zeros(L, dtype=bool),
                    jnp.zeros(L, dtype=jnp.int64),
                    jnp.zeros(R, dtype=bool),
                    jnp.full(R, jnp.inf, dtype=jnp.float64),
                    jnp.zeros(R, dtype=jnp.int64),
                    jnp.zeros(R, dtype=jnp.int64),
                    jnp.zeros(R, dtype=jnp.int64),
                    jnp.zeros(R, dtype=bool),
                    jnp.zeros((R, Nb), dtype=bool),
                    jnp.asarray(False),
                    jnp.asarray(False),
                )
                key = ("megastep", Cb, Nb, L, S, R, K, Tb, Gb, NCb, U,
                       use_pallas)
                dispatch._note_shape(key)
                dispatch.bound_jit_cache("megastep", fn, key)
                chunks = []
                del _CHUNK_WALL_S[:]  # capacity retry: re-profile the scan
                for ci in range(nchunk):
                    c0 = time.perf_counter()
                    sl = slice(ci * K, (ci + 1) * K)
                    carry, ys = fn(
                        carry,
                        jnp.asarray(ft_pad[sl]),
                        jnp.asarray(valid_pad[sl]),
                        jnp.asarray(vis_pad[sl]),
                        jnp.asarray(ci * K, dtype=jnp.int64),
                        scalars,
                        tables,
                        use_pallas=use_pallas,
                        interpret=interpret,
                    )
                    jax.block_until_ready(ys)  # compute, then time the pull
                    x0 = time.perf_counter()
                    chunks.append(jax.device_get(ys))
                    _LAST_XFER_S += time.perf_counter() - x0
                    _CHUNK_WALL_S.append(time.perf_counter() - c0)
                x0 = time.perf_counter()
                of_slots = bool(jax.device_get(carry[-2]))
                of_ring = bool(jax.device_get(carry[-1]))
                _LAST_XFER_S += time.perf_counter() - x0
                if not (of_slots or of_ring):
                    ys = tuple(
                        np.concatenate([c[f] for c in chunks], axis=0)[:T]
                        for f in range(len(chunks[0]))
                    )
                    x0 = time.perf_counter()
                    final_applied = np.asarray(jax.device_get(carry[0]))
                    _LAST_XFER_S += time.perf_counter() - x0
                    return _assemble(
                        plan, seed_applied, ys, final_applied,
                        plan.d_vc, plan.d_cu,
                    )
                # Divergence: grow the flagged capacity and retry; past the
                # caps, hand the run to the host reference.
                grew = False
                if of_slots and S < s_max:
                    S = min(S * 2, s_max)
                    R = min(max(R, dispatch.bucket(4 * L * S)), RING_CAP)
                    grew = True
                if of_ring and R < RING_CAP:
                    R = min(R * 2, RING_CAP)
                    grew = True
                if not grew:
                    _LAST_FALLBACK = "device-capacity"
                    return None
    except Exception as e:
        # Intentionally broad: whatever kills the device backend (XLA,
        # driver, shape divergence), the host reference takes over and the
        # result stays bit-exact — but the reason is recorded, not dropped.
        _LAST_FALLBACK = f"device-error: {e!r}"
        return None
