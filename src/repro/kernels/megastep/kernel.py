"""Pallas kernel for the mega-step's per-lane busy-chain slot sweep.

One program per VA/CR lane: each program replays its lane's padded slot
list for one tick — the VA chain step at the shared fused-FC arrival time,
the CR chain step at ``va_end + d_vc``, the per-lane uniform draw for the
verdict — exactly the float sequence of ``ref._LaneChain.step``.  The math
is pure f64 adds/compares (no multiplies, so no FMA contraction hazard),
which is what makes the kernel bit-identical to the numpy chain.

The sweep is inherently sequential per lane (slot ``s+1``'s start depends
on slot ``s``'s end), so the kernel is a ``fori_loop`` over slots with the
chain state in scalars; lanes are the grid.  Validated in interpret mode
against the jnp inner-scan in ``ops`` (see ``tests/test_megastep_props``).

The engine chooses this kernel from the chain state's dtype, not by trying
to compile it (``ops.lane_chain_uses_pallas``).  The TPU compiler refuses
64-bit operands in a Pallas call, and the chain state is f64, so the jnp
slot scan is the lane chain on every platform.  Its ``(1, S)`` slot blocks
do not fit the TPU's (8, 128) tiling either: the kernel runs interpreted
only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lane_chain_tick_pallas"]

# params layout: [t_arr, xi_va, xi_cr, d_vc, d_cu, p_tp]
N_PARAMS = 6


def _kernel(
    real_ref, has_ref, vab_ref, vaa_ref, crb_ref, cra_ref, draws_ref,
    unif_ref, par_ref,
    vab_o, vaa_o, crb_o, cra_o, draws_o,
    vend_o, qva_o, vafu_o, cend_o, qcr_o, crfu_o, auv_o, pos_o,
):
    t_arr = par_ref[0]
    xi_va = par_ref[1]
    xi_cr = par_ref[2]
    d_vc = par_ref[3]
    d_cu = par_ref[4]
    p_tp = par_ref[5]
    S = real_ref.shape[1]
    U = unif_ref.shape[0]

    def body(s, state):
        b_v, a_v, b_c, a_c, dr = state
        real = real_ref[0, s] != 0
        has = has_ref[0, s] != 0
        # VA chain (all slots of a tick share the fused-FC arrival).
        fu_v = t_arr >= b_v
        st_v = jnp.where(a_v != 0, b_v, t_arr + (b_v - t_arr))
        end_v = jnp.where(fu_v, t_arr + xi_va, st_v + xi_va)
        q_v = jnp.where(fu_v, 0.0, st_v - t_arr)
        b_v = jnp.where(real, end_v, b_v)
        a_v = jnp.where(real, jnp.where(fu_v, 0, 1), a_v)
        # CR chain.
        arr_c = end_v + d_vc
        fu_c = arr_c >= b_c
        st_c = jnp.where(a_c != 0, b_c, arr_c + (b_c - arr_c))
        end_c = jnp.where(fu_c, arr_c + xi_cr, st_c + xi_cr)
        q_c = jnp.where(fu_c, 0.0, st_c - arr_c)
        b_c = jnp.where(real, end_c, b_c)
        a_c = jnp.where(real, jnp.where(fu_c, 0, 1), a_c)
        # Verdict: one draw from the lane's position in the shared stream
        # per sourced frame that carries the entity.
        u = unif_ref[jnp.minimum(dr, U - 1)]
        drawn = jnp.logical_and(real, has)
        pos = jnp.logical_and(drawn, u <= p_tp)
        dr = dr + drawn.astype(dr.dtype)
        vend_o[0, s] = end_v
        qva_o[0, s] = q_v
        vafu_o[0, s] = fu_v.astype(jnp.int32)
        cend_o[0, s] = end_c
        qcr_o[0, s] = q_c
        crfu_o[0, s] = fu_c.astype(jnp.int32)
        auv_o[0, s] = end_c + d_cu
        pos_o[0, s] = pos.astype(jnp.int32)
        return b_v, a_v, b_c, a_c, dr

    state = (vab_ref[0], vaa_ref[0], crb_ref[0], cra_ref[0], draws_ref[0])
    b_v, a_v, b_c, a_c, dr = jax.lax.fori_loop(0, S, body, state)
    vab_o[0] = b_v
    vaa_o[0] = a_v
    crb_o[0] = b_c
    cra_o[0] = a_c
    draws_o[0] = dr


def lane_chain_tick_pallas(
    real, has, va_b, va_armed, cr_b, cr_armed, draws, uniforms, params,
    *, interpret: bool = False,
):
    """One tick's chain sweep for every lane.

    ``real/has``: (L, S) bool padded slot occupancy / entity visibility;
    ``va_b/cr_b``: (L,) f64 busy-until; ``va_armed/cr_armed``: (L,) bool;
    ``draws``: (L,) int64 per-lane draw counters; ``uniforms``: (U,) f64;
    ``params``: (6,) f64 ``[t_arr, xi_va, xi_cr, d_vc, d_cu, p_tp]``.

    Returns the updated chain state plus per-slot ``(L, S)`` outputs
    ``(va_end, q_va, va_fused, cr_end, q_cr, cr_fused, a_uv, positive)``,
    bit-identical to the jnp inner scan in :mod:`.ops`.
    """
    L, S = real.shape
    U = uniforms.shape[0]
    f64 = jnp.float64
    i32 = jnp.int32
    i64 = draws.dtype

    lane_state = pl.BlockSpec((1,), lambda l: (l,))
    lane_slots = pl.BlockSpec((1, S), lambda l: (l, 0))
    shared_u = pl.BlockSpec((U,), lambda l: (0,))
    shared_p = pl.BlockSpec((N_PARAMS,), lambda l: (0,))

    outs = pl.pallas_call(
        _kernel,
        grid=(L,),
        in_specs=[
            lane_slots, lane_slots,               # real, has
            lane_state, lane_state,               # va_b, va_armed
            lane_state, lane_state,               # cr_b, cr_armed
            lane_state,                           # draws
            shared_u, shared_p,                   # uniforms, params
        ],
        out_specs=[
            lane_state, lane_state, lane_state, lane_state, lane_state,
            lane_slots, lane_slots, lane_slots, lane_slots, lane_slots,
            lane_slots, lane_slots, lane_slots,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L,), f64),      # va_b
            jax.ShapeDtypeStruct((L,), i32),      # va_armed
            jax.ShapeDtypeStruct((L,), f64),      # cr_b
            jax.ShapeDtypeStruct((L,), i32),      # cr_armed
            jax.ShapeDtypeStruct((L,), i64),      # draws
            jax.ShapeDtypeStruct((L, S), f64),    # va_end
            jax.ShapeDtypeStruct((L, S), f64),    # q_va
            jax.ShapeDtypeStruct((L, S), i32),    # va_fused
            jax.ShapeDtypeStruct((L, S), f64),    # cr_end
            jax.ShapeDtypeStruct((L, S), f64),    # q_cr
            jax.ShapeDtypeStruct((L, S), i32),    # cr_fused
            jax.ShapeDtypeStruct((L, S), f64),    # a_uv
            jax.ShapeDtypeStruct((L, S), i32),    # positive
        ],
        interpret=interpret,
    )(
        real.astype(i32), has.astype(i32),
        va_b, va_armed.astype(i32), cr_b, cr_armed.astype(i32), draws,
        uniforms, params,
    )
    (vab, vaa, crb, cra, dr,
     va_end, q_va, va_fu, cr_end, q_cr, cr_fu, a_uv, pos) = outs
    return (
        vab, vaa != 0, crb, cra != 0, dr,
        va_end, q_va, va_fu != 0, cr_end, q_cr, cr_fu != 0, a_uv, pos != 0,
    )
