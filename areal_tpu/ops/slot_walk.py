"""The live-slot walk of the recurrent decode kernels (``ssm_state_update``,
``gdn_state_update``, ``kda_state_update``), written once.

Each of those kernels advances the state of the LIVE slots of one layer by one
token, in place: the stacked state ``[layers, slots, *slot_shape]`` stays
where it lies (``memory_space=ANY``, aliased to the output) and a slot's
2-4 MB come into VMEM, are updated head by head, and go back to the same rows.
Two or three operations a byte: the walk is bound by its copies. It keeps a
ring of ``RING`` VMEM buffers, each one slot's state updated in place, with
``AHEAD`` slots' fetches in flight before the slot that is computed and one
slot's store behind it:

    warm-up   fetch 0 .. AHEAD - 1
    trip t    wait fetch t
              wait store t + AHEAD - RING   (its buffer is the next fetch's)
              start fetch t + AHEAD
              body(order[t], ring[t % RING])    the kernel's own head loop
              start store t
    drain     wait the last RING stores

``order``'s first ``n`` entries are distinct, so no slot is fetched while it
is stored; a slot that is not among them is neither read nor written.

Why four and two (my chip runs, PR 50; PERF.md section 7): a stream that is
read and written back moves 656 GB/s both ways together on a v5e, 80% of its
819, whether XLA copies it or this walk does, and the walk reaches that with
two fetches ahead (328 GB/s a direction against 305 with one); a deeper ring,
or a slot's copy issued as four or eight descriptors, reads the same.

The helper binds ``jax.lax`` primitives only (``kda_state_update``'s body is
held to that: every ``jnp`` operator of a traced value is a jitted call traced
apart on the benchmark machine's host, PERF.md PR 45).
"""

from __future__ import annotations

import math
from typing import Callable

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RING = 4  # buffers, each one slot's state
AHEAD = 2  # fetches in flight before the slot being computed


def ring_scratch(slot_shape: tuple[int, ...], dtype) -> tuple:
    """The launch's scratch for the walk: the ring, and one DMA semaphore a
    buffer and direction (``walk_live_slots``'s ``ring``, ``isem``, ``osem``)."""
    sems = pltpu.SemaphoreType.DMA((RING,))
    return (pltpu.VMEM((RING, *slot_shape), dtype), sems, sems)


def ring_bytes(slot_shape: tuple[int, ...], dtype) -> int:
    """What the ring takes of VMEM: a buffer's lanes padded to whole tiles of
    128, its sublanes to a 32-bit tile's rows."""
    *lead, rows, lanes = slot_shape
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // itemsize)
    return RING * math.prod(lead) * (-(-rows // sub) * sub) * (-(-lanes // 128) * 128) * itemsize


def walk_live_slots(
    order_ref,  # SMEM [S] int32: live slots first
    n,  # scalar int32: how many of them
    li,  # scalar int32: the layer
    state_hbm,  # ANY [L, S, *slot_shape]
    state_out,  # ANY, the same buffer
    ring,  # VMEM [RING, *slot_shape]
    isem,  # DMA semaphores [RING]
    osem,  # DMA semaphores [RING]
    body: Callable,  # body(slot, buf): update ``buf`` [*slot_shape] in place, write the slot's outputs
) -> None:
    def fetch(t):
        b = lax.rem(t, RING)
        return pltpu.make_async_copy(state_hbm.at[li, order_ref[t]], ring.at[b], isem.at[b])

    def store(t):
        b = lax.rem(t, RING)
        return pltpu.make_async_copy(ring.at[b], state_out.at[li, order_ref[t]], osem.at[b])

    for i in range(AHEAD):
        pl.when(lax.gt(n, i))(lambda i=i: fetch(i).start())

    def trip(t, carry):
        fetch(t).wait()
        nxt = lax.add(t, AHEAD)

        @pl.when(lax.lt(nxt, n))
        def _next():
            @pl.when(lax.ge(nxt, RING))
            def _free():  # the store that last left the next fetch's buffer
                store(lax.sub(nxt, RING)).wait()

            fetch(nxt).start()

        body(order_ref[t], ring.at[lax.rem(t, RING)])
        store(t).start()
        return carry

    lax.fori_loop(0, n, trip, 0)
    for back in range(RING, 0, -1):  # the stores no trip waited for

        @pl.when(lax.ge(n, back))
        def _drain(back=back):
            store(lax.sub(n, back)).wait()
