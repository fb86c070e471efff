"""The sparse-expert FFN of a decode step over the TOUCHED experts only: one
Pallas launch a layer that walks a list of experts and reads the weights of
no other.

``models/moe.py _experts_dense`` runs every held expert on every row with
gate 0 for the rows that did not choose it: three plain matmuls that stream
each weight once, and nothing to win while every expert is chosen by some
row. Where a full batch gives an expert a handful of rows (64 rows x top-6
over a router of 128: 3), a batch that is not full or a router that is not
even leaves half the held experts with no live row at all, and their weights
are read and multiplied by a gate of exactly 0. This launch is that form's
arithmetic, term by term, over the experts in a compacted list:

    for e in touched:  out += round(silu(round(x @ Wg[e])) * round(x @ Wu[e]) * gate[e]) @ Wd[e]

(``round``: to the rows' type, as the dense form's matmuls come out; silu,
the product and the gate in float32; the down projection accumulated in
float32 over experts). An expert left out has gate 0 on every live row: its
term is exactly 0, so the sum is the same sum; only the order of the float32
additions over experts differs from XLA's.

The launch is ``ops/paged_latent_attention.py``'s: the expert stacks
``[layers, E_loc, D, F]`` / ``[layers, E_loc, F, D]`` stay where they lie
(``memory_space=ANY``: a layer's slice of them handed to a custom call would
be COPIED out of the stack first, 151 MB a layer at 16 experts of [2048,
768]), the layer's index, the list and its length as scalars in SMEM, a ring
of ``_NBUF`` VMEM buffers of one expert's three matrices so that the next
experts' copies are in flight while one multiplies, a semaphore a matrix so
that the gate product starts when ``Wg`` is there. All ``T`` rows go through
every listed expert: at 64 rows an expert is 0.6 GFLOP (3 us on a v5e) under
9.44 MB (11.5 us), so compacting rows is worth no code.

An expert whose three matrices do not fit the ring twice (6144 x 2048: 75 MB
an expert, 128 MB of VMEM) goes through in equal PARTS of its width
(``width_parts``): the gated product is a sum over the width, so a part is a
smaller expert with the same gate, ``Wg[:, part]``, ``Wu[:, part]``,
``Wd[part, :]``, and the list is walked part by part. One part (every expert
that fits) is the launch as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# experts' matrices in VMEM at once: one multiplied, the next in flight. A third
# buffer is worth nothing (tools/moe_probe --nbuf 3 on the v5e, PR 38: 62.3 /
# 111.1 / 159.7 / 209.6 us a layer at 4 / 8 / 12 / 16 experts of [2048, 768]
# against 60.9 / 110.6 / 161.1 / 209.7): one copy of 9.4 MB in flight fills the
# memory's bandwidth, and an expert's arithmetic is a quarter of its copy
_NBUF = 2
# bytes of expert matrices the ring may hold: under the launch's 100 MB beside rows, gates and activations
_RING_BYTES = 48 << 20


def width_parts(D: int, F: int, itemsize: int, nbuf: int = _NBUF) -> int:
    """Equal parts of an expert's width F that go through the ring one at a
    time: the fewest whose three matrices fit it ``nbuf`` times, each whole
    lane tiles. 1 for [2048, 768] (18.9 MB), 4 for [6144, 2048] (151 MB)."""
    for parts in range(1, F // 128 + 1):
        if F % (parts * 128) == 0 and nbuf * 3 * D * (F // parts) * itemsize <= _RING_BYTES:
            return parts
    return max(1, F // 128) if F % 128 == 0 else 1


def _touched_kernel(
    layer_ref,  # SMEM [1] int32
    ids_ref,  # SMEM [E_loc] int32: the touched experts' local ids, compacted to the front
    n_ref,  # SMEM [1] int32: how many of them count
    x_ref,  # VMEM [T, D]
    gate_ref,  # VMEM [E_loc, T, 1] f32: each expert's gate for each row, 0 where the row did not choose it
    wg_hbm,  # ANY [layers, E_loc, D, F]
    wu_hbm,  # ANY [layers, E_loc, D, F]
    wd_hbm,  # ANY [layers, E_loc, F, D]
    o_ref,  # VMEM [T, D] f32
    bg,  # VMEM [nbuf, D, F / parts]
    bu,  # VMEM [nbuf, D, F / parts]
    bd,  # VMEM [nbuf, F / parts, D]
    sems,  # DMA [3, nbuf]
    *,
    parts: int,  # equal parts of the width an expert goes through in (``width_parts``)
):
    li, n = layer_ref[0], n_ref[0] * parts
    nbuf = bg.shape[0]
    stacks, bufs = (wg_hbm, wu_hbm, wd_hbm), (bg, bu, bd)
    Fp = bg.shape[2]

    def copy(i, m):
        """The copy of matrix ``m`` of the walk's i-th item (the list's
        ``i // parts``-th expert, part ``i % parts`` of its width), built
        identically to start it and to wait for it."""
        src = stacks[m].at[li, ids_ref[i // parts]]
        if parts > 1:
            part = pl.ds(pl.multiple_of((i % parts) * Fp, 128), Fp)
            src = src.at[part, :] if m == 2 else src.at[:, part]
        return pltpu.make_async_copy(src, bufs[m].at[i % nbuf], sems.at[m, i % nbuf])

    def start(i):
        for m in range(3):
            copy(i, m).start()

    for i in range(nbuf - 1):  # fill the ring but for the buffer expert 0 frees
        pl.when(i < n)(lambda i=i: start(i))

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)  # no expert touched: exact zeros

    def expert(i, _):
        @pl.when(i + nbuf - 1 < n)
        def _prefetch():  # into the buffer expert i-1 has just left
            start(i + nbuf - 1)

        slot = i % nbuf
        x = x_ref[...]
        copy(i, 0).wait()
        g = jnp.dot(x, bg[slot], preferred_element_type=jnp.float32).astype(x.dtype)
        copy(i, 1).wait()
        u = jnp.dot(x, bu[slot], preferred_element_type=jnp.float32).astype(x.dtype)
        y = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32) * gate_ref[ids_ref[i // parts]]
        copy(i, 2).wait()
        o_ref[...] += jnp.dot(y.astype(x.dtype), bd[slot], preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n, expert, None)


def touched_expert_ffn(
    x: jax.Array,  # [T, D] rows
    gate: jax.Array,  # [T, E_loc] f32: each local expert's gate for each row, 0 where the row did not choose it
    wg: jax.Array,  # [layers, E_loc, D, F]
    wu: jax.Array,  # [layers, E_loc, D, F]
    wd: jax.Array,  # [layers, E_loc, F, D]
    layer: jax.Array,  # scalar int32
    touched: jax.Array,  # [E_loc] int32: local ids of the experts to read, compacted to the front
    n_touched: jax.Array,  # scalar int32: how many of them
    *,
    interpret: bool = False,
) -> jax.Array:
    """sum over the first ``n_touched`` experts e of ``touched`` of
    ``(silu(x @ wg[layer, e]) * (x @ wu[layer, e]) * gate[:, e]) @ wd[layer,
    e]``: [T, D] float32, the arithmetic of ``moe._experts_dense``. No
    expert beyond the list is read, nor any other layer's; an empty list
    costs the launch and returns exact zeros."""
    T, D = x.shape
    n_layers, E_loc, _, F = wg.shape
    nbuf = _NBUF
    if wg.shape != (n_layers, E_loc, D, F) or wu.shape != wg.shape or wd.shape != (n_layers, E_loc, F, D):
        raise ValueError(f"expert stacks [layers, E, {D}, F] x 2 and [layers, E, F, {D}] expected, got {wg.shape}, {wu.shape}, {wd.shape}")
    if gate.shape != (T, E_loc) or touched.shape != (E_loc,):
        raise ValueError(f"gates [{T}, {E_loc}] and a list of {E_loc} ids expected, got {gate.shape}, {touched.shape}")
    pad = -T % 16  # whole sublane tiles of the rows' type
    if pad:
        x, gate = jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(gate, ((0, pad), (0, 0)))
    rows = T + pad
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    parts = width_parts(D, F, wg.dtype.itemsize, nbuf)
    Fp = F // parts
    w_bytes = nbuf * 3 * D * Fp * wg.dtype.itemsize
    # rows, output, gates (a lane tile an expert and row) and the activations
    io_bytes = rows * (D * (x.dtype.itemsize + 4) + E_loc * 128 * 4 + 4 * Fp * 4)
    out = pl.pallas_call(
        functools.partial(_touched_kernel, parts=parts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[vmem, vmem, hbm, hbm, hbm],
            out_specs=vmem,
            grid=(1,),
            scratch_shapes=(
                pltpu.VMEM((nbuf, D, Fp), wg.dtype),
                pltpu.VMEM((nbuf, D, Fp), wu.dtype),
                pltpu.VMEM((nbuf, Fp, D), wd.dtype),
                pltpu.SemaphoreType.DMA((3, nbuf)),
            ),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, max(16 << 20, w_bytes + 2 * io_bytes + (8 << 20)))),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        name="moe_touched_experts",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        touched.astype(jnp.int32),
        jnp.asarray(n_touched, jnp.int32).reshape(1),
        x,
        gate.astype(jnp.float32).T[:, :, None],
        wg,
        wu,
        wd,
    )
    return out[:T] if pad else out
