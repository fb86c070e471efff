"""Pallas TPU kernel: a decode step's K and V rows of one layer written into
the paged pools with ONE launch over the LIVE slots, in place.

A decode step appends one token a slot: a row of ``head_dim`` values per KV
head for K and for V (and, under quantized pages, one scale each) at
``(layer, head, page[s], offset[s])``. Written as XLA scatters that is one
scatter per KV head and pool (a scatter over all heads re-lays the whole pool
out, PERF.md PR 21), and a scatter on the TPU walks its updates one by one:
0.10 us a row whatever its bytes, 1.4 ms of a 9.0 ms step at 128 slots x 2
heads x 28 layers (PERF.md, PR 29) — two rows in three for slots that hold no
request.

This kernel takes the stacked pools where they lie (``memory_space=ANY``,
aliased to its outputs), the layer index, each slot's page and offset and the
list of live slots as scalars, and slices ``ref.at[layer]`` itself, as
``paged_decode_attn`` does. A slot that is not on the list is neither read
nor written.

What the chip's compiler asked for: a pool's second-minor dimension is the
token, and a 16- or 8-bit dtype packs two or four token rows into one 32-bit
sublane, so ONE row is not a DMA slice (refused for bf16, int8 and fp8 alike:
"slice shape must be aligned to tiling (8)"). So a slot's write is a
read-modify-write of the aligned TILE of ``_TILE_ROWS`` rows that holds its
row, every KV head in one strided copy: all live slots' tiles come into VMEM
together, each gets its row set there (in 32 bits: exact for every page
dtype), and all go back together. A scale row ``[1, page_size]`` (lane-major,
ops/paged_attention_q8.py) is its own tile: the token's lane is set.

A pool's reads share one DMA semaphore, and a DMA semaphore counts bytes,
not copies: one wait says that a tile's worth of bytes has landed somewhere,
not that THIS tile has. So no tile is touched before as many waits as there
are reads have returned, which only all of them landed can pay for (the
writes likewise, before the launch ends).

The read-modify-write is sound because no two LIVE slots write the same
page in one step: the page a slot appends to is private to it (a group's
siblings share only full prompt pages, the engine copies the partial one),
and the one page many slots do share, trash page 0, belongs to the slots this
kernel skips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of the tile a slot's row is written through: the smallest slice of the
# token dimension the chip's compiler takes for bf16, int8 and fp8 pools alike
_TILE_ROWS = 8


def _compute_dtype(dtype) -> jnp.dtype:
    """The 32-bit type a pool's rows are handed over and set in (a head's
    row is then a static sublane of the slot's [KH, hd], which a packed dtype
    does not give). Every page dtype round-trips through it exactly."""
    return jnp.dtype(jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32)


def _kernel(
    layer_ref,  # SMEM [1] int32
    order_ref,  # SMEM [S] int32: live slots first
    n_live_ref,  # SMEM [1] int32
    page_ref,  # SMEM [S] int32: the page slot s writes into
    off_ref,  # SMEM [S] int32: the row of that page
    *refs,
    n_pages: int,  # page pools [L, KH, N, psz, hd], rows VMEM [S, KH, hd] in 32 bits
    n_scales: int,  # scale pools [L, KH, N, 1, psz], rows SMEM [S, KH]
):
    n_pools = n_pages + n_scales
    rows = refs[:n_pools]
    pools = refs[2 * n_pools : 3 * n_pools]  # the outputs: the inputs' own buffers
    bufs = refs[3 * n_pools : 4 * n_pools]
    rsem, wsem = refs[4 * n_pools :]
    li = layer_ref[0]
    n = n_live_ref[0]

    def tile(p, t):
        """(the HBM tile slot ``order[t]`` writes through in pool p, its VMEM
        buffer)."""
        s = order_ref[t]
        pg = page_ref[s]
        if p < n_pages:
            base = pl.multiple_of(off_ref[s] // _TILE_ROWS * _TILE_ROWS, _TILE_ROWS)
            return pools[p].at[li, :, pg, pl.ds(base, _TILE_ROWS), :], bufs[p].at[t]
        return pools[p].at[li, :, pg], bufs[p].at[t]

    def read(p, t):
        hbm, vmem = tile(p, t)
        return pltpu.make_async_copy(hbm, vmem, rsem.at[p])

    def write(p, t):
        hbm, vmem = tile(p, t)
        return pltpu.make_async_copy(vmem, hbm, wsem.at[p])

    def fetch(t, carry):
        for p in range(n_pools):
            read(p, t).start()
        return carry

    def landed(t, carry):
        for p in range(n_pools):
            read(p, t).wait()
        return carry

    def put(t, carry):
        s = order_ref[t]
        off = off_ref[s]
        for p in range(n_pools):
            buf = bufs[p]
            num_heads = buf.shape[1]
            if p < n_pages:
                hd = buf.shape[-1]
                new = rows[p][s]  # [KH, hd]
                here = jax.lax.broadcasted_iota(jnp.int32, (_TILE_ROWS, hd), 0) == off % _TILE_ROWS
                for h in range(num_heads):
                    buf[t, h] = jnp.where(here, new[h : h + 1], buf[t, h].astype(new.dtype)).astype(buf.dtype)
            else:
                here = jax.lax.broadcasted_iota(jnp.int32, (1, buf.shape[-1]), 1) == off
                for h in range(num_heads):
                    buf[t, h] = jnp.where(here, rows[p][s, h], buf[t, h])
            write(p, t).start()
        return carry

    def drain(t, carry):
        for p in range(n_pools):
            write(p, t).wait()
        return carry

    jax.lax.fori_loop(0, n, fetch, 0)
    jax.lax.fori_loop(0, n, landed, 0)  # every read, before the first tile is touched
    jax.lax.fori_loop(0, n, put, 0)
    jax.lax.fori_loop(0, n, drain, 0)


def paged_kv_write(
    pages: tuple[jax.Array, ...],  # pools [n_layers, KH, N, psz, hd]; updated in place
    page_rows: tuple[jax.Array, ...],  # [S, KH, hd] each, of its pool's dtype
    layer: jax.Array,  # scalar int32
    write_page: jax.Array,  # [S] int32
    write_off: jax.Array,  # [S] int32, < psz
    order: jax.Array,  # [S] int32: the live slots first (``live_order``)
    n_live: jax.Array,  # scalar int32
    *,
    scales: tuple[jax.Array, ...] = (),  # pools [n_layers, KH, N, 1, psz], lane-major
    scale_rows: tuple[jax.Array, ...] = (),  # [S, KH] each
    interpret: bool = False,
) -> tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]:
    """(pages, scales) with ``pool[layer, :, write_page[s], write_off[s]] =
    rows[s]`` for the first ``n_live`` slots s of ``order`` and nothing else
    touched. No two of those slots may write the same page (module
    docstring)."""
    S = write_page.shape[0]
    for pool, new in zip(pages, page_rows, strict=True):
        _, KH, _, psz, hd = pool.shape
        if new.shape != (S, KH, hd) or new.dtype != pool.dtype:
            raise ValueError(f"rows {new.dtype}{list(new.shape)} for a pool {pool.dtype}{list(pool.shape)} and {S} slots")
        if psz % _TILE_ROWS:
            raise ValueError(f"page_size {psz} is not a multiple of the {_TILE_ROWS}-row tile")
    for pool, new in zip(scales, scale_rows, strict=True):
        if pool.shape[3] != 1 or new.shape != (S, pool.shape[1]):
            raise ValueError(f"scale rows {list(new.shape)} for a lane-major scale pool {list(pool.shape)}")

    pools = [*pages, *scales]
    rows = [r.astype(_compute_dtype(r.dtype)) for r in page_rows] + [r.astype(s.dtype) for r, s in zip(scale_rows, scales)]
    n_pages, n_scales = len(pages), len(scales)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    # a tile a slot and pool: _TILE_ROWS token rows of a page pool, the one row of a scale pool
    tiles = [(S, p.shape[1], _TILE_ROWS if i < n_pages else 1, p.shape[-1]) for i, p in enumerate(pools)]
    n_scalars = 5
    # a (1, lanes) tile still fills 8 sublanes, as do the rows' KV heads
    vmem_bytes = sum(4 * S * max(8, r.shape[1]) * r.shape[2] for r in rows[:n_pages])
    vmem_bytes += sum(s * kh * max(8, t) * lanes * p.dtype.itemsize for (s, kh, t, lanes), p in zip(tiles, pools))
    out = pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, n_scales=n_scales),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            in_specs=[vmem] * n_pages + [smem] * n_scales + [anyspace] * len(pools),
            out_specs=[anyspace] * len(pools),
            grid=(1,),
            scratch_shapes=(
                *(pltpu.VMEM(t, p.dtype) for t, p in zip(tiles, pools)),
                pltpu.SemaphoreType.DMA((len(pools),)),  # reads, one a pool: waited out in full before use
                pltpu.SemaphoreType.DMA((len(pools),)),  # writes
            ),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * vmem_bytes + (8 << 20)))),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # pool p, counted after the scalars and the rows, is output p
        input_output_aliases={n_scalars + len(pools) + p: p for p in range(len(pools))},
        name="paged_kv_write",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        order.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        write_page.astype(jnp.int32),
        write_off.astype(jnp.int32),
        *rows,
        *pools,
    )
    return tuple(out[:n_pages]), tuple(out[n_pages:])
