"""Pallas TPU kernel: one decode step of a gated-delta-rule layer's recurrence
over the LIVE slots only, in place.

A decode step advances each live slot's state ``S`` [K, V] a head by one
token and reads it out:

    S' = alpha S;  r = S'^T k;  S = S' + k (beta (v - r))^T;  o = S^T q

a read of the decayed state BEFORE a rank-one write, which the state-space
update (``ops/ssm_state_update.py``) has not. It is some ten operations a
byte: bound by reading and writing the state, 2.2 MB a slot and layer at
Olmo-Hybrid-7B's 30 heads of 96 x 192 in float32. As there, the stacked state
stays where it lies (``memory_space=ANY``, aliased to the output) and the
kernel walks the list of live slots (``ops/slot_walk.py``: a ring of four
VMEM buffers updated in place, two slots' fetches in flight before the slot
that is computed, one slot's store behind it); a slot that is not on the list
is neither read nor written.

Layout (what the chip asks for):
  - the state is held PACKED, ``[layers, slots, H / p, K, p * V]``: p heads
    side by side on the lanes, p the least number that makes ``p * V`` whole
    128-lane tiles (``head_pack``: 2 at V = 192). Alone, a head's 192 values
    would be padded to 256 lanes in HBM and in every copy, a third more
    bytes; ``v`` and ``o`` are in their natural flat order this way;
  - per group of p heads the tile is [K, p * V]; ``alpha`` and ``beta`` are
    scalars a head (SMEM), set on their head's lanes by a select; ``k`` and
    ``q`` have to be COLUMNS [K, 1] a head, so the caller hands them
    transposed, [S, K, H], and a head's column is a static lane slice;
  - both reductions run over the sublanes (K), so ``r`` and ``o`` are rows;
  - groups and heads are static loops: a lane slice at a traced offset does
    not lower.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.slot_walk import ring_bytes, ring_scratch, walk_live_slots


def head_pack(num_heads: int, v_dim: int) -> int:
    """Heads held side by side on the lanes of one state tile: the least
    divisor p of ``num_heads`` with ``p * v_dim`` a multiple of 128 lanes
    (1 where there is none: the tile is then padded)."""
    for p in range(1, num_heads + 1):
        if num_heads % p == 0 and (p * v_dim) % 128 == 0:
            return p
    return 1


def pack_state(s: jax.Array, p: int) -> jax.Array:
    """[..., H, K, V] -> [..., H / p, K, p * V]."""
    *lead, H, K, V = s.shape
    s = s.reshape(*lead, H // p, p, K, V)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, H // p, K, p * V)


def unpack_state(s: jax.Array, p: int) -> jax.Array:
    """[..., H / p, K, p * V] -> [..., H, K, V]."""
    *lead, G, K, PV = s.shape
    s = s.reshape(*lead, G, K, p, PV // p)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, G * p, K, PV // p)


def _kernel(
    order_ref,  # SMEM [S] int32: live slots first
    n_live_ref,  # SMEM [1] int32
    layer_ref,  # SMEM [1] int32
    alpha_ref,  # SMEM [S, H] f32: exp(g)
    beta_ref,  # SMEM [S, H] f32
    q_t_ref,  # VMEM [S, K, H] f32: q transposed (normalised and scaled)
    k_t_ref,  # VMEM [S, K, H] f32
    v_ref,  # VMEM [S, G, p * V] f32
    state_hbm,  # ANY [L, S, G, K, p * V]
    o_ref,  # VMEM out [S, G, p * V] f32
    state_out,  # ANY, the same buffer as state_hbm
    ring,  # VMEM [RING, G, K, p * V]
    isem,
    osem,
):
    _, groups, k_dim, pv = ring.shape
    pack = alpha_ref.shape[1] // groups
    v_dim = pv // pack
    o_ref[...] = jnp.zeros_like(o_ref)

    # which of the group's heads a lane belongs to
    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (1, pv), 1) // v_dim
    head_of = jax.lax.broadcasted_iota(jnp.int32, (k_dim, pv), 1) // v_dim

    def slot(s, buf):
        q_t = q_t_ref[s]  # [K, H]
        k_t = k_t_ref[s]
        for g in range(groups):
            h0 = g * pack
            alpha = jnp.full((1, pv), alpha_ref[s, h0], jnp.float32)
            beta = jnp.full((1, pv), beta_ref[s, h0], jnp.float32)
            kk = jnp.broadcast_to(k_t[:, h0 : h0 + 1], (k_dim, pv))
            qq = jnp.broadcast_to(q_t[:, h0 : h0 + 1], (k_dim, pv))
            for j in range(1, pack):
                h = h0 + j
                alpha = jnp.where(head_of_row == j, alpha_ref[s, h], alpha)
                beta = jnp.where(head_of_row == j, beta_ref[s, h], beta)
                kk = jnp.where(head_of == j, k_t[:, h : h + 1], kk)
                qq = jnp.where(head_of == j, q_t[:, h : h + 1], qq)
            decayed = buf[g].astype(jnp.float32) * alpha  # [K, p V]
            read = jnp.sum(decayed * kk, axis=0, keepdims=True)  # [1, p V]: S'^T k
            u = beta * (v_ref[s, g : g + 1, :] - read)
            new = decayed + kk * u
            buf[g] = new.astype(buf.dtype)
            o_ref[s, g : g + 1, :] = jnp.sum(new * qq, axis=0, keepdims=True)

    walk_live_slots(order_ref, n_live_ref[0], layer_ref[0], state_hbm, state_out, ring, isem, osem, slot)


def gdn_state_update_stacked(
    state: jax.Array,  # [n_layers, S, H / p, K, p * V], float32 or bfloat16; updated in place
    layer: jax.Array,  # scalar int32
    q: jax.Array,  # [S, H, K] f32, L2-normalised and scaled
    k: jax.Array,  # [S, H, K] f32, L2-normalised
    v: jax.Array,  # [S, H, V] f32
    alpha: jax.Array,  # [S, H] f32: exp(g), the state's decay
    beta: jax.Array,  # [S, H] f32: the write strength
    order: jax.Array,  # [S] int32: the live slots first (``live_order``)
    n_live: jax.Array,  # scalar int32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(state with layer ``layer`` advanced one token for the first
    ``n_live`` slots of ``order``, o [S, H, V] float32 = S_new^T q, zero for
    the other slots)."""
    _, S, G, K, PV = state.shape
    H, V = v.shape[1], v.shape[2]
    assert G * PV == H * V and q.shape == (S, H, K), (state.shape, q.shape, v.shape)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    buf_bytes = ring_bytes((G, K, PV), state.dtype)
    o, out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[smem, smem, vmem, vmem, vmem, anyspace],
            out_specs=[vmem, anyspace],
            grid=(1,),
            scratch_shapes=ring_scratch((G, K, PV), state.dtype),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, buf_bytes + (32 << 20))),
        out_shape=(jax.ShapeDtypeStruct((S, G, PV), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={8: 1},  # the state, counted after the three scalars
        name="gdn_state_update",
        interpret=interpret,
    )(
        order.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        alpha.astype(jnp.float32),
        beta.astype(jnp.float32),
        jnp.swapaxes(q.astype(jnp.float32), 1, 2),
        jnp.swapaxes(k.astype(jnp.float32), 1, 2),
        v.astype(jnp.float32).reshape(S, G, PV),
        state,
    )
    return out, o.reshape(S, H, V)
