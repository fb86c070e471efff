"""Pallas TPU kernel: one decode step of a state-space layer's recurrence over
the LIVE slots only, in place.

A decode step advances each live slot's state ``S`` [H, P, N] by one token,
``S <- exp(dt A) S + (dt x) B^T``, and reads it out, ``y = S C``. That is
three operations a byte: the step is bound by reading and writing the state,
2 MiB a slot and layer at the published Mamba-2 sizes in float32. Written in
XLA over the slot-indexed state array it touches every slot, and in
closed-loop GRPO traffic two slots in three hold no request (PERF.md, PR 26).

This kernel takes the stacked state ``[n_layers, S, H, P, N]`` where it lies
(``memory_space=ANY``, aliased to its output) with the layer index and the
list of live slots as scalars, and walks that list (``ops/slot_walk.py``: a
ring of four VMEM buffers updated in place, two slots' fetches in flight
before the slot that is computed, one slot's store behind it). A slot that is
not on the list is neither read nor written: it keeps its state bit for bit.

Layout notes (what the chip's compiler asked for):
  - per head the tile is [P, N] with N on the lanes; ``exp(dt A)`` is a
    scalar a head (SMEM), ``dt x`` has to be a COLUMN [P, 1] a head, so the
    caller hands it transposed, [S, P, H], and a head's column is a static
    lane slice; ``y`` leaves the same way, [S, P, H], one lane a head;
  - heads are a static loop: a lane slice at a traced offset does not lower;
  - a slot's heads are updated in one pass and read out in a second, from a
    float32 copy of the new state (what a bfloat16 state has rounded away):
    spreading a head's ``dt x`` over the lanes and summing its ``S C`` over
    them both run on the cross-lane units, and head by head each waited for
    the other, 7.5 us a slot where the copies take 6.4 (PERF.md, PR 50).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.slot_walk import ring_bytes, ring_scratch, walk_live_slots


def _kernel(
    order_ref,  # SMEM [S] int32: live slots first
    n_live_ref,  # SMEM [1] int32
    layer_ref,  # SMEM [1] int32
    decay_ref,  # SMEM [S, H] f32: exp(dt A)
    dtx_t_ref,  # VMEM [S, P, H] f32: (dt x) transposed
    b_ref,  # VMEM [S, G, N] f32
    c_ref,  # VMEM [S, G, N] f32
    ssm_hbm,  # ANY [L, S, H, P, N]
    y_t_ref,  # VMEM out [S, P, H] f32
    ssm_out,  # ANY, the same buffer as ssm_hbm
    ring,  # VMEM [RING, H, P, N]
    isem,
    osem,
    fresh,  # VMEM [H, P, N] f32: the slot's new state before it is rounded to the state's dtype
):
    _, num_heads, head_dim, _ = ring.shape
    groups = b_ref.shape[1]
    y_t_ref[...] = jnp.zeros_like(y_t_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (head_dim, num_heads), 1)

    def slot(s, buf):
        # every head's update, then every head's read-out (the module's layout notes): 3,697 bundles a slot head by
        # head, 2,642 this way, the same expressions
        dtx_t = dtx_t_ref[s]  # [P, H]
        for h in range(num_heads):
            g = h // (num_heads // groups)
            state = buf[h].astype(jnp.float32)  # [P, N]
            new = state * decay_ref[s, h] + dtx_t[:, h : h + 1] * b_ref[s, g : g + 1, :]
            buf[h] = new.astype(buf.dtype)
            fresh[h] = new
        y_t = jnp.zeros((head_dim, num_heads), jnp.float32)
        for h in range(num_heads):
            g = h // (num_heads // groups)
            col = jnp.sum(fresh[h] * c_ref[s, g : g + 1, :], axis=-1, keepdims=True)  # [P, 1]
            y_t = jnp.where(lane == h, col, y_t)
        y_t_ref[s] = y_t

    walk_live_slots(order_ref, n_live_ref[0], layer_ref[0], ssm_hbm, ssm_out, ring, isem, osem, slot)


def ssm_state_update_stacked(
    ssm: jax.Array,  # [n_layers, S, H, P, N], float32 or bfloat16; updated in place
    layer: jax.Array,  # scalar int32
    x: jax.Array,  # [S, H, P] f32
    b: jax.Array,  # [S, G, N] f32
    c: jax.Array,  # [S, G, N] f32
    dt: jax.Array,  # [S, H] f32, after softplus
    a: jax.Array,  # [H] f32, negative
    order: jax.Array,  # [S] int32: the live slots first (``live_order``)
    n_live: jax.Array,  # scalar int32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(ssm with layer ``layer`` advanced one token for the first ``n_live``
    slots of ``order``, y [S, H, P] float32 = S_new C, zero for the other
    slots). The skip term ``D x`` is the caller's."""
    _, S, H, P, N = ssm.shape
    decay = jnp.exp(dt * a)
    dtx_t = jnp.swapaxes(dt[..., None] * x, 1, 2)  # [S, P, H]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    buf_bytes = ring_bytes((H, P, N), ssm.dtype) + 4 * H * P * N
    y_t, out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem, anyspace],
            out_specs=[vmem, anyspace],
            grid=(1,),
            scratch_shapes=(*ring_scratch((H, P, N), ssm.dtype), pltpu.VMEM((H, P, N), jnp.float32)),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, buf_bytes + (24 << 20))),
        out_shape=(jax.ShapeDtypeStruct((S, P, H), jnp.float32), jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)),
        input_output_aliases={7: 1},  # the state, counted after the three scalars
        name="ssm_state_update",
        interpret=interpret,
    )(
        order.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        decay.astype(jnp.float32),
        dtx_t.astype(jnp.float32),
        b.astype(jnp.float32),
        c.astype(jnp.float32),
        ssm,
    )
    return out, jnp.swapaxes(y_t, 1, 2)
