"""Pallas TPU kernel: the sampler's first level, ONE read of the ``[S, V]``
logits in the tiling the ``lm_head`` matmul wrote them.

The hierarchical inverse-CDF sampler (``inference/decode_programs.py``) needs the
probability mass of each block of the vocabulary, a row, before it reads any
block in full. Written in XLA that was ``scaled.reshape(S, NB, V // NB)`` and a
log-sum-exp over the last axis, with ``NB`` a divisor of ``V``: 151,936 = 128 x
1,187, and 1,187 is no multiple of the 128 lanes of a tile, so the TPU compiler
made the reshape a full pass, relaid it out in a second (``copy``) and reduced
in a third: 78 MB each way a decode step at 128 slots, 4.4% of the device's
time in the cell most users run (PERF.md, PR 46). Two other XLA forms failed
outright (factors turned round: two copies, PR 35; ``lax.reduce_window`` over
padded windows: a copy, then out of VMEM).

Here the partition follows the tiling, not the divisors: blocks of ``W`` lanes,
``W`` a multiple of 128 chosen from ``V`` alone (``block_width``), the last one
partial. The launch walks the row's blocks as they lie, one ``(S, W)`` block a
grid step, masks the columns past ``V`` and writes each block's maximum and its
sum of ``exp(x - maximum)`` into lane ``j`` of two ``[S, 128]`` outputs that
stay in VMEM for the whole launch. No reshape, no pad, no copy of the logits.

The body is written in ``jax.lax`` primitives only: every ``jnp`` function or
operator of a traced value inside a kernel body is a jitted call traced apart,
1-3 ms each of a start-up's host time (PERF.md, PR 45), and every sampling
program traces this launch.

The sampler's next level reads ONE block a row, the block the first level
chose. XLA has no cheap form of that either once the blocks are windows of
the row and not rows of a reshaped copy: ``take_along_axis`` over the window's
columns compiles to a gather of single elements (2.7 ms a step at 128 slots x
2,048 columns), a gather of ``(1, W)`` slices to a ``while`` of S trips (0.15
ms; my chip runs, PR 46). ``vocab_block_pick`` is a second launch of one grid
step a row: the step's input block is the ``(8, W)`` block of the row's group
and of its chosen block (the block index comes from scalar prefetch), and it
keeps the one row: 13 us a step there.

``vocab_block_stats_xla`` and ``vocab_block_pick_xla`` are the same partition
in ``jnp`` over a padded copy of the row (whose blocks ARE rows, so the gather
is the cheap kind), for where no compiled kernel runs (the CPU, a vocabulary
sharded over a mesh) and as the kernels' reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # blocks a row at most: their statistics fill the lanes of one tile
_SUBLANES = 8  # rows of a float32 tile: the fewest a block of the logits can hold
_MIN_WIDTH = 2048  # 16 lane tiles a row: 1 MB a grid step at 128 slots


def block_width(vocab: int) -> int:
    """Lanes a block of the sampler's partition: 2,048, doubled until the
    row holds at most 128 blocks (75 blocks at 151,936 columns, 98 at 200,064,
    10 at 19,360, one below 2,049)."""
    width = _MIN_WIDTH
    while -(-vocab // width) > LANES:
        width *= 2
    return width


def _kernel(x_ref, max_ref, sum_ref, *, vocab: int, width: int):
    j = pl.program_id(0)
    x = x_ref[...]  # [S, W]; the last block's columns past V hold anything
    held = lax.sub(vocab, lax.mul(j, width))  # columns of this block inside the row
    x = lax.select(lax.lt(lax.broadcasted_iota(jnp.int32, x.shape, 1), held), x, lax.full_like(x, -jnp.inf))
    m = lax.expand_dims(lax.reduce_max(x, (1,)), (1,))  # [S, 1]
    # a block of -inf alone (no model's logits; a caller's mask might): exp(-inf - 0) = 0, not nan
    base = lax.select(lax.eq(m, -jnp.inf), lax.full_like(m, 0.0), m)
    p = lax.exp(lax.sub(x, lax.broadcast_in_dim(base, x.shape, (0, 1))))
    s = lax.expand_dims(lax.reduce_sum(p, (1,)), (1,))

    @pl.when(lax.eq(j, 0))
    def _():  # lanes no block writes read as a block without mass
        max_ref[...] = lax.full(max_ref.shape, -jnp.inf, jnp.float32)
        sum_ref[...] = lax.full(sum_ref.shape, 0.0, jnp.float32)

    here = lax.eq(lax.broadcasted_iota(jnp.int32, max_ref.shape, 1), j)
    max_ref[...] = lax.select(here, lax.broadcast_in_dim(m, max_ref.shape, (0, 1)), max_ref[...])
    sum_ref[...] = lax.select(here, lax.broadcast_in_dim(s, sum_ref.shape, (0, 1)), sum_ref[...])


def vocab_block_stats(scaled: jax.Array, *, interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(block maxima, block sums of ``exp(x - maximum)``), each ``[S, 128]``
    float32, of the blocks of ``block_width(V)`` columns of ``scaled`` ``[S,
    V]`` float32; block ``j`` in lane ``j``, the lanes past the last block
    ``-inf`` and 0."""
    S, V = scaled.shape
    width = block_width(V)
    stats = jax.ShapeDtypeStruct((S, LANES), jnp.float32)
    whole = pl.BlockSpec((S, LANES), lambda j: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, vocab=V, width=width),
        grid=(pl.cdiv(V, width),),
        in_specs=[pl.BlockSpec((S, width), lambda j: (0, j))],
        out_specs=[whole, whole],
        out_shape=[stats, stats],
        name="vocab_block_stats",
        interpret=interpret,
    )(scaled.astype(jnp.float32))


def _pick_kernel(block_ref, x_ref, out_ref):
    del block_ref  # read by the index maps
    r = lax.rem(pl.program_id(0), x_ref.shape[0])
    out_ref[pl.ds(r, 1), :] = x_ref[pl.ds(r, 1), :]


def vocab_block_pick(scaled: jax.Array, block: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``[S, W]`` float32: of each row of ``scaled`` ``[S, V]`` the block
    ``block[s]`` (int32, below the row's number of blocks) of ``W =
    block_width(V)`` columns. What a partial last block's columns past ``V``
    hold is not defined."""
    S, V = scaled.shape
    width = block_width(V)
    rows = min(_SUBLANES, S)  # a step's rows: the tile of the row it keeps
    return pl.pallas_call(
        _pick_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[pl.BlockSpec((rows, width), lambda s, block: (lax.div(s, rows), block[s]))],
            # the same block for the steps of one tile's rows: written back once they all are in
            out_specs=pl.BlockSpec((rows, width), lambda s, block: (lax.div(s, rows), 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, width), jnp.float32),
        name="vocab_block_pick",
        interpret=interpret,
    )(block.astype(jnp.int32), scaled.astype(jnp.float32))


def _padded_blocks(scaled: jax.Array) -> jax.Array:
    """``[S, blocks, W]``: the row padded with ``-inf`` to whole blocks."""
    S, V = scaled.shape
    width = block_width(V)
    nb = -(-V // width)
    x = jnp.pad(scaled.astype(jnp.float32), ((0, 0), (0, nb * width - V)), constant_values=-jnp.inf)
    return x.reshape(S, nb, width)


def vocab_block_stats_xla(scaled: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``vocab_block_stats`` in ``jnp``: the same blocks, summed over a padded
    copy of the row."""
    x = _padded_blocks(scaled)
    m = x.max(-1)
    s = jnp.exp(x - jnp.where(jnp.isneginf(m), 0.0, m)[..., None]).sum(-1)
    lanes = ((0, 0), (0, LANES - x.shape[1]))
    return jnp.pad(m, lanes, constant_values=-jnp.inf), jnp.pad(s, lanes)


def vocab_block_pick_xla(scaled: jax.Array, block: jax.Array) -> jax.Array:
    """``vocab_block_pick`` in ``jnp``: a row of that padded copy (one jitted
    program makes the copy once for both)."""
    return jnp.take_along_axis(_padded_blocks(scaled), block[:, None, None], axis=1)[:, 0]
