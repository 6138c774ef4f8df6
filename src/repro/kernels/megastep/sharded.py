"""Sharded mega-step: the fused tick scan over a ``cameras`` device mesh.

The camera-block world — per-query activity masks (``applied``), the
visibility table, the spotlight distance/hop planes and the per-camera lane
map — lives sharded over a 1-D ``cameras`` mesh axis via ``shard_map``
(through :mod:`repro.distributed.compat`); the query registry state (tag
bits, modes, radius tables, last-seen cameras) and the lane/ring machinery
are replicated.  Per tick, only the **frontier** crosses shard boundaries:

* per-lane active counts — one ``all_gather`` of (D, L) ints, giving each
  shard the exclusive prefix that turns its local lane slots into global
  sink-order slots;
* lane min-camera ranks — one ``pmin`` of (L,) ints;
* the (lane, slot) occupancy/visibility/tag-mask rows — ``psum``/``pmax``
  of (L, S) and (L, S, Nb) frontier tables that exactly one shard writes
  per slot (scatter-disjoint, so integer reductions are exact);
* TL spotlight counts — ``psum`` of (Nb,) ints.

Per-query budget counters (sourced / positives) accumulate **locally** in
the scan carry and are all-reduced once per K-tick chunk — the trace
cadence — not per tick.

Everything float stays replicated and is computed in the reference order on
every shard, so the result is **bit-identical** to the single-device scan
(`ops.run_chain_device`) and therefore to the interpreted pipeline; the
tests gate exactly that across 1/2/4/8 emulated host devices.  The
collective volume is O(L·S·Nb + D·L) per tick — frontier rows, never the
O(C) world — and is reported per run via
:func:`last_collective_bytes_per_tick`.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import numpy as np

from .. import dispatch
from . import ops as _ops
from . import ref as _ref

__all__ = [
    "run_chain_sharded",
    "last_xfer_seconds",
    "last_chunk_seconds",
    "last_shards",
    "last_collective_bytes_per_tick",
]

_SHARDED_FNS: Dict[Tuple, object] = {}

_LAST_XFER_S = 0.0
_LAST_SHARDS = 1
_LAST_COLLECTIVE_BPT = 0.0
_LAST_ERROR = ""
# Per-chunk host wall of the most recent sharded scan (same contract as
# ``ops._CHUNK_WALL_S``): observability attribution, never a decision input.
_CHUNK_WALL_S: list = []


def last_xfer_seconds() -> float:
    return _LAST_XFER_S


def last_chunk_seconds() -> list:
    """Per-chunk wall times (seconds) of the most recent sharded scan, in
    chunk order; empty when the sharded path was never tried or fell back."""
    return list(_CHUNK_WALL_S)


def last_shards() -> int:
    """Shard count of the most recent successful run_chain_sharded call."""
    return _LAST_SHARDS


def last_collective_bytes_per_tick() -> float:
    """Analytic per-tick cross-shard traffic (bytes moved per device) of
    the most recent run: the frontier collectives listed in the module
    docstring, not the sharded world."""
    return _LAST_COLLECTIVE_BPT


def last_error() -> str:
    return _LAST_ERROR


def _build_sharded_chunk_fn(mesh, axis: str):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ...distributed.compat import shard_map

    D = mesh.shape[axis]

    def chunk(carry, ftimes_k, valid_k, vis_k, k0, scalars, tables):
        xi_fc, xi_va, xi_cr, d_fv, d_vc, d_cu, p_tp = scalars
        (lane_of, uniforms, modes, rgroup, r_tab, h_tab,
         cand_of_cam, dist_plane, hop_plane, qvalid, cvalid, slot_iota) = tables

        Nb = carry[0].shape[0]
        Cl = carry[0].shape[1]          # local camera-block width (Cb / D)
        L = carry[3].shape[0]
        S = slot_iota.shape[0]
        R = carry[8].shape[0]
        Tb = r_tab.shape[-1]
        U = uniforms.shape[0]
        INT_BIG = jnp.iinfo(jnp.int64).max
        I32_BIG = jnp.iinfo(jnp.int32).max

        lane_ids = jnp.arange(L, dtype=jnp.int64)
        cam0 = lax.axis_index(axis).astype(jnp.int64) * Cl
        cam_ids = cam0 + jnp.arange(Cl, dtype=jnp.int64)  # global ids
        q_shift = jnp.arange(Nb, dtype=jnp.uint64)
        shard_before = jnp.arange(D, dtype=jnp.int64) < lax.axis_index(axis)
        lane_onehot = lane_of[:, None] == lane_ids[None, :]   # (Cl, L)

        def tick_step(c, xs):
            (applied, ls_cam, ls_tick, va_b, va_armed, cr_b, cr_armed, draws,
             ring_valid, ring_auv, ring_tick, ring_gen, ring_cam, ring_pos,
             ring_mask, of_slots, of_ring, acc_src, acc_pos) = c
            now, valid, vis_row, i = xs
            k = k0 + i

            # ---- TL tick: replicated ring consume (same as ops) ---------- #
            do_tl = valid & (k >= 1)
            take = ring_valid & (ring_auv < now) & do_tl
            cand = take[:, None] & ring_mask & ring_pos[:, None]
            any_pos = cand.any(axis=0)
            tickv = jnp.where(cand, ring_tick[:, None], jnp.int64(-1))
            best_tick = tickv.max(axis=0)
            cand2 = cand & (ring_tick[:, None] == best_tick[None, :])
            auvv = jnp.where(cand2, ring_auv[:, None], jnp.inf)
            best_auv = auvv.min(axis=0)
            cand3 = cand2 & (ring_auv[:, None] == best_auv[None, :])
            genv = jnp.where(cand3, ring_gen[:, None], INT_BIG)
            win = jnp.argmin(genv, axis=0)
            upd = do_tl & any_pos
            ls_cam = jnp.where(upd, ring_cam[win], ls_cam)
            ls_tick = jnp.where(upd, best_tick, ls_tick)
            ring_valid = ring_valid & ~take

            # Spotlight over this shard's camera-block columns.
            kt = jnp.minimum(k, Tb - 1)
            lst = jnp.minimum(ls_tick, Tb - 1)
            src = jnp.maximum(cand_of_cam[ls_cam], 0)
            hops = h_tab[rgroup, lst, kt]
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
            tl_counts = jnp.where(
                do_tl,
                lax.psum(new_req.sum(axis=1, dtype=jnp.int64), axis),
                0,
            )
            tl_union = jnp.where(
                do_tl,
                lax.psum(new_req.any(axis=0).sum(dtype=jnp.int64), axis),
                0,
            )

            # ---- sourcing from the PREVIOUS tick's applied --------------- #
            bits = jnp.sum(
                jnp.where(applied, jnp.uint64(1) << q_shift[:, None],
                          jnp.uint64(0)),
                axis=0, dtype=jnp.uint64,
            )                                                     # (Cl,)
            active = applied.any(axis=0) & valid                  # (Cl,)
            act_lane = active[:, None] & lane_onehot              # (Cl, L)
            local_n = act_lane.sum(axis=0, dtype=jnp.int64)       # (L,)
            counts_all = lax.all_gather(local_n, axis)            # (D, L)
            # Exclusive prefix over shards: cameras are block-contiguous per
            # shard, so global sink order == (shard, local) order and each
            # local lane slot offsets by the active count on earlier shards.
            before = jnp.sum(
                jnp.where(shard_before[:, None], counts_all, 0), axis=0
            )                                                     # (L,)
            cum = jnp.cumsum(act_lane.astype(jnp.int64), axis=0)
            slot_l = jnp.take_along_axis(cum, lane_of[:, None], axis=1)[:, 0] - 1
            slot = slot_l + before[lane_of]                       # global slot
            n_l = counts_all.sum(axis=0)                          # (L,)
            of_slots = of_slots | (n_l.max() > S)
            # The TPU lowers no 64-bit all-reduce but a sum: camera ids and
            # their sentinels fit int32, so min/max reduce in int32.
            camv = jnp.where(act_lane, cam_ids[:, None], I32_BIG)
            min_cam = lax.pmin(camv.min(axis=0).astype(jnp.int32), axis)
            grank = jnp.sum(
                min_cam[None, :] < min_cam[:, None], axis=1, dtype=jnp.int64
            )

            # Frontier scatter: exactly one shard owns each (lane, slot), so
            # pmax/psum over scatter-disjoint tables reassemble exactly.
            ok = active & (slot < S)
            scat = jnp.where(ok, lane_of * S + slot, L * S)
            cam_at = lax.pmax(
                jnp.full(L * S, -1, dtype=jnp.int32).at[scat].set(
                    cam_ids.astype(jnp.int32), mode="drop"
                ),
                axis,
            ).reshape(L, S).astype(jnp.int64)
            real_ls = cam_at >= 0
            cam_c = jnp.maximum(cam_at, 0)
            has_ls = lax.psum(
                jnp.zeros(L * S, dtype=jnp.int32).at[scat].set(
                    vis_row.astype(jnp.int32), mode="drop"
                ),
                axis,
            ).reshape(L, S) > 0
            mask_flat = lax.psum(
                jnp.zeros((L * S, Nb), dtype=jnp.int32).at[scat].set(
                    applied.T.astype(jnp.int32), mode="drop"
                ),
                axis,
            ) > 0                                                 # (L*S, Nb)

            t_arr = (now + xi_fc) + d_fv

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
                slot_step, (va_b, va_armed, cr_b, cr_armed, draws), slot_iota,
            )
            (va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos) = (
                x.T for x in so
            )

            # ---- detection ring insertion (replicated, same as ops) ------ #
            real_flat = real_ls.reshape(-1)
            gen_flat = (
                (k * L + grank[:, None]) * S + slot_iota[None, :]
            ).reshape(-1)
            cam_flat = cam_c.reshape(-1)
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

            # ---- per-query budget counters: local accumulation ----------- #
            acc_src = acc_src + jnp.where(
                valid, applied.sum(axis=1, dtype=jnp.int64), 0
            )
            acc_pos = acc_pos + jnp.where(
                valid,
                (applied & vis_row[None, :]).sum(axis=1, dtype=jnp.int64),
                0,
            )

            c2 = (new_req, ls_cam, ls_tick, va_b, va_armed, cr_b, cr_armed,
                  draws, ring_valid, ring_auv, ring_tick, ring_gen, ring_cam,
                  ring_pos, ring_mask, of_slots, of_ring, acc_src, acc_pos)
            ys = (bits, tl_counts, tl_union, grank, cam_at, real_ls,
                  va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos)
            return c2, ys

        K = ftimes_k.shape[0]
        xs = (ftimes_k, valid_k, vis_k, jnp.arange(K, dtype=jnp.int64))
        src0, pos0 = carry[-2], carry[-1]
        carry2, ys = lax.scan(tick_step, carry, xs)
        # Budgets all-reduce once per chunk — the trace cadence.  The
        # incoming counters are already global (replicated), so only this
        # chunk's local delta is summed; psum-ing the running total would
        # multiply every prior chunk's count by the shard count.
        carry2 = carry2[:-2] + (
            src0 + lax.psum(carry2[-2] - src0, axis),
            pos0 + lax.psum(carry2[-1] - pos0, axis),
        )
        return carry2, ys

    # applied is camera-sharded; lane/ring state, the detection ring and
    # the query-side tables are replicated; the bits summary comes back
    # camera-sharded while every per-(lane, slot) summary is replicated.
    P_cam = P(None, axis)
    carry_specs = (
        P_cam,                                  # applied (Nb, Cb)
        P(), P(),                               # ls_cam, ls_tick
        P(), P(), P(), P(), P(),                # va/cr busy state + draws
        P(), P(), P(), P(), P(), P(), P(),      # detection ring
        P(), P(),                               # overflow flags
        P(), P(),                               # per-query budget counters
    )
    tables_specs = (
        P(axis),                                # lane_of (Cb,)
        P(), P(), P(), P(), P(),                # uniforms..h_tab (replicated)
        P(),                                    # cand_of_cam: indexed by the
                                                # replicated last-seen cam
        P_cam, P_cam,                           # dist/hop planes (NCb, Cb)
        P(), P(axis), P(),                      # qvalid, cvalid, slot_iota
    )
    ys_specs = (P_cam,) + (P(),) * 13
    fn = shard_map(
        chunk,
        mesh=mesh,
        in_specs=(carry_specs, P(), P(), P_cam, P(),
                  (P(),) * 7, tables_specs),
        out_specs=(carry_specs, ys_specs),
        # Every shard computes the identical replicated outputs through the
        # deterministic psum/pmax combines; the replication checker cannot
        # infer that across lax.scan.
        check=False,
    )
    return jax.jit(fn)


def _collective_bytes_per_tick(D: int, L: int, S: int, Nb: int) -> float:
    """Per-device bytes moved by the frontier collectives each tick."""
    return float(
        D * L * 8        # all_gather of per-lane active counts
        + L * 4          # pmin of lane min-camera
        + L * S * 4      # pmax of slot occupancy (cam_at)
        + L * S * 4      # psum of slot visibility
        + L * S * Nb * 4  # psum of slot tag masks
        + Nb * 8 + 8     # psum of TL counts + union size
    )


def run_chain_sharded(plan, seed_applied, rules) -> Optional[_ref.ChainOutput]:
    """Run the fused scan sharded over the mesh in ``rules``; None means
    "use the unsharded path" (reason in :func:`last_error`) — mesh lacks a
    ``cameras`` axis, a single device, a non-dividing camera bucket, or
    capacities exceeded.  Bit-identical to ``ops.run_chain_device``."""
    global _LAST_XFER_S, _LAST_SHARDS, _LAST_COLLECTIVE_BPT, _LAST_ERROR
    _LAST_ERROR = ""
    if plan.modes is None:
        _LAST_ERROR = "no-table-planes"
        return None
    import jax
    import jax.numpy as jnp

    mesh = rules.mesh
    axis = "cameras" if "cameras" in mesh.axis_names else None
    if axis is None:
        _LAST_ERROR = "no-cameras-axis"
        return None
    D = int(mesh.shape[axis])
    if D <= 1:
        # Single visible device: the unsharded scan IS the single-shard
        # path and is bit-identical by construction.
        _LAST_ERROR = "single-device"
        return None

    C = plan.num_cameras
    N = seed_applied.shape[0]
    L = plan.num_lanes
    T = len(plan.ftimes)
    Cb = dispatch.bucket(C)
    if Cb % D != 0:
        _LAST_ERROR = f"camera-bucket {Cb} % {D} shards != 0"
        return None
    Nb = min(dispatch.bucket(N), 64)
    if N > Nb:
        _LAST_ERROR = "queries>64"
        return None
    Tb = dispatch.bucket(T)
    K = min(dispatch.bucket(T), _ops.KMAX)
    nchunk = (T + K - 1) // K
    _LAST_XFER_S = 0.0
    del _CHUNK_WALL_S[:]

    try:
        if not _ops.x64_exact():
            _LAST_ERROR = "x64-emulated"
            return None
        with jax.enable_x64(True):
            fkey = (tuple(d.id for d in mesh.devices.flat), axis)
            fn = _SHARDED_FNS.get(fkey)
            if fn is None:
                fn = _build_sharded_chunk_fn(mesh, axis)
                _SHARDED_FNS[fkey] = fn

            tables_np, (Gb, NCb, U) = _ops._plan_device_tables(
                plan, jnp, Nb, Cb, Tb
            )
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

            S, R, s_max = _ops._initial_capacities(plan, seed_applied)
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
                    jnp.zeros(Nb, dtype=jnp.int64),
                    jnp.zeros(Nb, dtype=jnp.int64),
                )
                key = ("megastep-sharded", D, Cb, Nb, L, S, R, K, Tb, Gb,
                       NCb, U)
                dispatch._note_shape(key)
                dispatch.bound_jit_cache("megastep_sharded", fn, key)
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
                    )
                    jax.block_until_ready(ys)
                    x0 = time.perf_counter()
                    chunks.append(jax.device_get(ys))
                    _LAST_XFER_S += time.perf_counter() - x0
                    _CHUNK_WALL_S.append(time.perf_counter() - c0)
                x0 = time.perf_counter()
                of_slots = bool(jax.device_get(carry[15]))
                of_ring = bool(jax.device_get(carry[16]))
                _LAST_XFER_S += time.perf_counter() - x0
                if not (of_slots or of_ring):
                    ys = tuple(
                        np.concatenate([c[f] for c in chunks], axis=0)[:T]
                        for f in range(len(chunks[0]))
                    )
                    x0 = time.perf_counter()
                    final_applied = np.asarray(jax.device_get(carry[0]))
                    sourced = np.asarray(jax.device_get(carry[17]))[:N]
                    qpos = np.asarray(jax.device_get(carry[18]))[:N]
                    _LAST_XFER_S += time.perf_counter() - x0
                    _LAST_SHARDS = D
                    _LAST_COLLECTIVE_BPT = _collective_bytes_per_tick(
                        D, L, S, Nb
                    )
                    return _ops._assemble(
                        plan, seed_applied, ys, final_applied,
                        plan.d_vc, plan.d_cu,
                        counters=(sourced, qpos),
                    )
                grew = False
                if of_slots and S < s_max:
                    S = min(S * 2, s_max)
                    R = min(max(R, dispatch.bucket(4 * L * S)), _ops.RING_CAP)
                    grew = True
                if of_ring and R < _ops.RING_CAP:
                    R = min(R * 2, _ops.RING_CAP)
                    grew = True
                if not grew:
                    _LAST_ERROR = "capacity"
                    return None
    except Exception as e:
        # Same contract as the unsharded scan: any backend failure falls
        # back (here: to the unsharded device path), reason recorded.
        _LAST_ERROR = repr(e)
        return None
