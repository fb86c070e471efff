"""Pallas TPU kernel: one decode step of a ``kda`` layer's recurrence (a delta
rule whose state decays by a factor of its own every key channel) over the
LIVE slots only, in place.

A decode step advances each live slot's state ``S`` [K, V] a head by one
token and reads it out:

    S' = diag(d) S;  r = S'^T k;  S = S' + k (beta (v - r))^T;  o = S^T q

``d = exp(a)`` in (0, 1]^K: a row of the state a key channel, each with its
own decay. ``ops/gdn_state_update.py`` is the same walk with ONE decay a head
(``alpha [S, H]``, a scalar from SMEM set on a tile's lanes); here the decay
is a COLUMN [K, 1] a head beside ``k`` and ``q``, and a head's tile [K, V] is
whole as it lies (V = 128 lanes as published: no packing of heads). It is the
TWIN of that kernel and not a wider form of it: a column operand there would
change the launch every ``gdn`` model compiles (its operands, its VMEM, its
name in a trace), and cell 6's ``decode_gdn_state_roofline`` and ``setup_s``
are held where they are (PERF.md section 6, PR 47).

Some ten operations a byte: bound by reading and writing the state, 4.19 MB a
slot and layer at Solar-Open2's 64 heads of 128 x 128 in float32. The stacked
state stays where it lies (``memory_space=ANY``, aliased to the output) and
the kernel walks the list of live slots (``ops/slot_walk.py``: a ring of four
VMEM buffers updated in place, two slots' fetches in flight before the slot
that is computed, one slot's store behind it); a slot that is not on the list
is neither read nor written.

Layout: the state ``[layers, slots, H, K, V]``; ``beta`` a scalar a head
(SMEM); ``q``, ``k`` and ``d`` columns a head, so the caller hands them
transposed, [S, K, H], and a head's column is a static lane slice; both
reductions run over the sublanes (K), so ``r`` and ``o`` are rows; heads are
a static loop (a lane slice at a traced offset does not lower).

The body binds ``jax.lax`` primitives only: on the benchmark machine's host
every ``jnp`` function or operator of a traced value inside a kernel body is
a jitted call traced apart, 1-3 ms each (PERF.md, PR 45), and the loop over
64 heads would bind some six hundred of them a trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.slot_walk import ring_bytes, ring_scratch, walk_live_slots


def _kernel(
    order_ref,  # SMEM [S] int32: live slots first
    n_live_ref,  # SMEM [1] int32
    layer_ref,  # SMEM [1] int32
    beta_ref,  # SMEM [S, H] f32
    q_t_ref,  # VMEM [S, K, H] f32: q transposed (normalised and scaled)
    k_t_ref,  # VMEM [S, K, H] f32
    d_t_ref,  # VMEM [S, K, H] f32: exp(a) transposed, the decay a key channel
    v_ref,  # VMEM [S, H, V] f32
    state_hbm,  # ANY [L, S, H, K, V]
    o_ref,  # VMEM out [S, H, V] f32
    state_out,  # ANY, the same buffer as state_hbm
    ring,  # VMEM [RING, H, K, V]
    isem,
    osem,
):
    _, heads, k_dim, v_dim = ring.shape
    tile = (k_dim, v_dim)
    o_ref[...] = lax.full(o_ref.shape, 0.0, jnp.float32)

    def column(ref, s, h):  # a head's column [K, 1] of a transposed operand, over the tile's lanes
        return lax.broadcast_in_dim(ref[s, :, h : h + 1], tile, (0, 1))

    def row_sum(x):  # over the sublanes (K): [K, V] -> [1, V]
        return lax.expand_dims(lax.reduce_sum(x, (0,)), (0,))

    def slot(s, buf):
        for h in range(heads):
            kk, qq = column(k_t_ref, s, h), column(q_t_ref, s, h)
            decayed = lax.mul(lax.convert_element_type(buf[h], jnp.float32), column(d_t_ref, s, h))  # [K, V]: diag(d) S
            read = row_sum(lax.mul(decayed, kk))  # [1, V]: S'^T k
            beta = lax.full((1, v_dim), beta_ref[s, h], jnp.float32)
            w = lax.mul(beta, lax.sub(v_ref[s, h : h + 1, :], read))
            new = lax.add(decayed, lax.mul(kk, lax.broadcast_in_dim(w, tile, (0, 1))))
            buf[h] = lax.convert_element_type(new, buf.dtype)
            o_ref[s, h : h + 1, :] = row_sum(lax.mul(new, qq))

    walk_live_slots(order_ref, n_live_ref[0], layer_ref[0], state_hbm, state_out, ring, isem, osem, slot)


def kda_state_update_stacked(
    state: jax.Array,  # [n_layers, S, H, K, V], float32 or bfloat16; updated in place
    layer: jax.Array,  # scalar int32
    q: jax.Array,  # [S, H, K] f32, L2-normalised and scaled
    k: jax.Array,  # [S, H, K] f32, L2-normalised
    v: jax.Array,  # [S, H, V] f32
    decay: jax.Array,  # [S, H, K] f32: exp(a), the state's decay a key channel
    beta: jax.Array,  # [S, H] f32: the write strength
    order: jax.Array,  # [S] int32: the live slots first (``live_order``)
    n_live: jax.Array,  # scalar int32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(state with layer ``layer`` advanced one token for the first
    ``n_live`` slots of ``order``, o [S, H, V] float32 = S_new^T q, zero for
    the other slots). The launch site's equations, a live slot and head:
    ``S' = diag(decay) S``, ``w = beta (v - S'^T k)``, ``S = S' + k w^T``,
    ``o = S^T q``."""
    _, S, H, K, V = state.shape
    assert q.shape == k.shape == decay.shape == (S, H, K) and v.shape == (S, H, V), (state.shape, q.shape, v.shape)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lanes = -(-V // 128) * 128
    # the ring of slots' states, and the operands that stay in VMEM for the whole launch (a transposed column
    # operand's H lanes padded to whole tiles)
    buf_bytes = ring_bytes((H, K, V), state.dtype)
    operand_bytes = 4 * S * (3 * K * (-(-H // 128) * 128) + 2 * H * lanes)
    f32 = jnp.float32
    o, out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[smem, vmem, vmem, vmem, vmem, anyspace],
            out_specs=[vmem, anyspace],
            grid=(1,),
            scratch_shapes=ring_scratch((H, K, V), state.dtype),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, buf_bytes + operand_bytes + (16 << 20))),
        out_shape=(jax.ShapeDtypeStruct((S, H, V), f32), jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={8: 1},  # the state, counted after the three scalars
        name="kda_state_update",
        interpret=interpret,
    )(
        order.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        beta.astype(f32),
        jnp.swapaxes(q.astype(f32), 1, 2),
        jnp.swapaxes(k.astype(f32), 1, 2),
        jnp.swapaxes(decay.astype(f32), 1, 2),
        v.astype(f32),
        state,
    )
    return out, o
