"""Paged decode attention over LATENT pages: the absorbed form of latent
(MLA) attention, one Pallas launch a layer over the stacked pool.

A latent-attention layer leaves ONE row a token behind, ``[c | k_r | 0]``
(``kv_lora_rank`` + ``qk_rope_head_dim`` values in whole 128-lane tiles:
576 stored as 640), shared by every query head; a decode step's queries come
here already absorbed, ``[W_UK^T q_nope | q_rope | 0]`` (models/hybrid.py
``mla_absorbed_query``), so a head's score against a cached token is one dot
product over the stored row, and its output is the probabilities' sum over
the row's first ``value_lanes`` lanes: the key IS the value. The caller
moves ``W_UV`` across that sum afterwards.

The launch is ``ops/paged_attention_q8.py``'s: the stacked pool
[n_layers, 1, N, psz, lanes] where it lies (``memory_space=ANY``), the layer
index, the page table and the work list (``decode_schedule``: one item per
live slot and block of pages that holds tokens of it, the same for every
layer of a step) as scalars, a ring of ``_NBUF`` VMEM buffers with two items'
copies in flight while one is computed, flash-style online softmax in f32
carried through the item loop. What differs:

  - ONE pool. An item's pages are fetched once and serve as keys (all
    lanes) and as values (the first ``value_lanes``): handing the pool to
    the K/V kernel as both would read every page twice.
  - every query head attends to the same rows: 32 query rows an item where
    the K/V kernel has a group of 6-8, one matmul of [H, lanes] x [lanes,
    block] and one of [H, block] x [block, value_lanes].
  - both matmuls take the operands in the pages' own type (bfloat16 pages:
    bfloat16 queries and probabilities) and accumulate in float32. By the
    shapes the layer is bound by memory in bfloat16 only: 2 x 32 x (576 +
    512) operations against 1,152 B a cached token = 60 a byte, a quarter of
    the v5e's ridge; upcast to float32 as the K/V kernel does (free at 6-8
    rows) the 32 rows would sit at the ridge. So the probabilities are
    rounded to the pages' type before they meet the values: one rounding of
    2^-9 a term that the K/V kernel does not make, and the tests' tolerance
    for bfloat16 pages says so (float32 pages: none).

Where the layer has a learned index (DeepSeek-V3.2's: ONE index key a cached
token in a pool of its own, 128 lanes, on the same pages), a step first
scores every cached token of every live slot (``paged_index_scores_stacked``:
the same launch and work list over the index pool, [Hi, d] x [d, block] a
block, relu, the heads' weighted sum), the caller selects (models/hybrid.py
``select_top``), and the latent launch takes the selection as a mask
(``select``): every page that holds tokens is still fetched, the unselected
rows meet a probability of exactly 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention_q8 import _MASK_VALUE, _NBUF, decode_schedule


def _latent_kernel(
    lengths_ref,  # SMEM [S] int32: valid tokens per slot
    pidx_ref,  # SMEM [S * pps] int32: flat page table
    layer_ref,  # SMEM [1] int32
    item_slot_ref,  # SMEM: decode_schedule()
    item_block_ref,
    num_items_ref,  # SMEM [1] int32
    q_ref,  # VMEM [S, H, lanes]: absorbed queries, in the pages' type
    *refs,  # with ``selected``: VMEM [S, pps * psz] int32, 1 where the slot attends to the cached token; then:
    # kv_hbm ANY [n_layers, 1, N, psz, lanes]; o_ref VMEM [S, H, value_lanes] f32; buf VMEM [_NBUF, ppcb, psz, lanes]; sems DMA [_NBUF]
    ppcb: int,
    pps: int,
    value_lanes: int,
    sm_scale: float,
    selected: bool,
):
    sel_ref = refs[0] if selected else None
    kv_hbm, o_ref, buf, sems = refs[-4:]
    li = layer_ref[0]
    num_items = num_items_ref[0]
    psz, lanes = kv_hbm.shape[-2:]
    nbuf = buf.shape[0]
    H = q_ref.shape[1]
    bk = ppcb * psz

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)  # a slot no item names keeps these
    if ppcb > 1:
        # a slot's last block fetches only the pages that hold tokens and
        # computes over the whole block: what the other pages' buffers hold
        # meets a probability of exactly 0 and must be finite for that
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def copies(t, go):
        """``go`` (start or wait) on item t's page copies, built identically
        both times; a buffer's copies share one semaphore (it counts bytes)."""
        b, i, slot = item_slot_ref[t], item_block_ref[t], t % nbuf
        held = (lengths_ref[b] - i * bk + psz - 1) // psz  # pages with tokens

        def page(j):
            pg = pidx_ref[b * pps + i * ppcb + j]
            go(pltpu.make_async_copy(kv_hbm.at[li, 0, pg], buf.at[slot, j], sems.at[slot]))

        page(0)
        for j in range(1, ppcb):
            pl.when(j < held)(functools.partial(page, j))

    for t in range(nbuf - 1):  # fill the ring but for the slot item 0 frees

        @pl.when(t < num_items)
        def _warm(t=t):
            copies(t, lambda c: c.start())

    def item(t, carry):
        @pl.when(t + nbuf - 1 < num_items)
        def _prefetch():  # into the buffer item t-1 has just left
            copies(t + nbuf - 1, lambda c: c.start())

        b, i, slot = item_slot_ref[t], item_block_ref[t], t % nbuf
        length = lengths_ref[b]
        first = i == 0
        m_prev, l_prev, acc = carry
        m_prev = jnp.where(first, _MASK_VALUE, m_prev)
        l_prev = jnp.where(first, 0.0, l_prev)
        acc = jnp.where(first, 0.0, acc)
        copies(t, lambda c: c.wait())
        rows = buf[slot].reshape(bk, lanes)  # keys; their first value_lanes lanes the values
        logits = jax.lax.dot_general(
            q_ref[b], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [H, bk]
        col = i * bk + jax.lax.broadcasted_iota(jnp.int32, (H, bk), 1)
        seen = col < length
        if selected:  # the tokens the slot's index chose: the others are fetched with their page and masked
            seen = seen & (sel_ref[pl.ds(b, 1), pl.ds(pl.multiple_of(i * bk, bk), bk)] != 0)
        logits = jnp.where(seen, logits, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        if selected:  # a block may hold no chosen token: the mask value is then its maximum, and exp(0) must not count
            p = jnp.where(seen, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_lanes], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc = acc * corr + pv

        @pl.when(i == (length + bk - 1) // bk - 1)
        def _store():  # the slot's last block
            o_ref[b] = (acc / l_new).astype(o_ref.dtype)

        return m_new, l_new, acc

    init = (
        jnp.full((H, 1), _MASK_VALUE, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, value_lanes), jnp.float32),
    )
    jax.lax.fori_loop(0, num_items, item, init)


def paged_latent_attention_stacked(
    q: jax.Array,  # [S, H, lanes]: absorbed queries [W_UK^T q_nope | q_rope | 0], RAW (``sm_scale`` applied here)
    pages: jax.Array,  # [n_layers, 1, N, psz, lanes]: latent rows [c | k_r | 0]
    layer: jax.Array,  # scalar int32
    lengths: jax.Array,  # i32 [S]
    page_indices: jax.Array,  # i32 [S, pages_per_sequence]
    *,
    value_lanes: int,  # the row's first lanes that are its value (kv_lora_rank)
    pages_per_compute_block: int,
    sm_scale: float,
    schedule: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    select: jax.Array | None = None,  # bool [S, pages_per_sequence * psz]: the cached tokens each slot attends to
    interpret: bool = False,
) -> jax.Array:
    """sum_s softmax_s(q . row_s * sm_scale) row_s[:value_lanes] over each
    slot's cached rows of layer ``layer``: [S, H, value_lanes] float32. A
    slot of length 0 costs nothing and returns exact zeros. ``schedule`` is
    ``decode_schedule()`` of the same lengths, table width and block size.
    With ``select`` the softmax and the sum run over the selected tokens
    only (every slot with tokens must select one): the MASKED form of a
    sparse read, every page that holds tokens still fetched."""
    S, H, lanes = q.shape
    n_layers, one, _, page_size, lanes_p = pages.shape
    pps = page_indices.shape[1]
    ppcb = pages_per_compute_block
    if one != 1 or lanes_p != lanes:
        raise ValueError(f"latent pages [layers, 1, N, psz, {lanes}] expected, got {list(pages.shape)}")
    if value_lanes % 128 or value_lanes > lanes:
        raise ValueError(f"value_lanes {value_lanes} is not whole lane tiles of a {lanes}-lane row")
    if pps % ppcb:
        raise ValueError(f"pages_per_sequence={pps} not divisible by pages_per_compute_block={ppcb}")
    if schedule is None:
        schedule = decode_schedule(lengths, pps, page_size, ppcb)
    max_items = S * (pps // ppcb)
    if schedule[0].shape != (max_items,):
        raise ValueError(f"schedule of {schedule[0].shape[0]} items, {max_items} expected")
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    item_bytes = pages.dtype.itemsize
    vmem_bytes = _NBUF * ppcb * page_size * lanes * item_bytes + S * max(8, H) * (lanes * item_bytes + value_lanes * 4)
    chosen = () if select is None else (select.astype(jnp.int32),)
    if chosen and select.shape != (S, pps * page_size):
        raise ValueError(f"select {list(select.shape)} for {S} slots of {pps} pages of {page_size}")
    vmem_bytes += sum(4 * c.size for c in chosen)
    return pl.pallas_call(
        functools.partial(
            _latent_kernel, ppcb=ppcb, pps=pps, value_lanes=value_lanes, sm_scale=float(sm_scale), selected=bool(chosen)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[vmem] * (1 + len(chosen)) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            grid=(1,),
            scratch_shapes=(
                pltpu.VMEM((_NBUF, ppcb, page_size, lanes), pages.dtype),
                pltpu.SemaphoreType.DMA((_NBUF,)),
            ),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * vmem_bytes + (8 << 20)))),
        out_shape=jax.ShapeDtypeStruct((S, H, value_lanes), jnp.float32),
        name="paged_latent_attn",
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        page_indices.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *schedule,
        q.astype(pages.dtype),
        *chosen,
        pages,
    )


def _index_kernel(
    lengths_ref,  # SMEM [S] int32
    pidx_ref,  # SMEM [S * pps] int32: flat page table
    layer_ref,  # SMEM [1] int32
    item_slot_ref,  # SMEM: decode_schedule()
    item_block_ref,
    num_items_ref,  # SMEM [1] int32
    q_ref,  # VMEM [S, Hi, d]: the index's queries, in the pages' type
    w_ref,  # VMEM [S, Hi, 1] f32: the heads' weights
    idx_hbm,  # ANY [n_layers, 1, N, psz, d]: one index key a cached token
    o_ref,  # VMEM [S, pps * psz] f32
    buf,  # VMEM [_NBUF, ppcb, psz, d]
    sems,  # DMA [_NBUF]
    *,
    ppcb: int,
    pps: int,
):
    li = layer_ref[0]
    num_items = num_items_ref[0]
    psz, d = idx_hbm.shape[-2:]
    nbuf = buf.shape[0]
    bk = ppcb * psz
    if ppcb > 1:  # a last block's pages without tokens are not fetched: what is computed over them must be finite
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def copies(t, go):  # as ``_latent_kernel``'s
        b, i, slot = item_slot_ref[t], item_block_ref[t], t % nbuf
        held = (lengths_ref[b] - i * bk + psz - 1) // psz

        def page(j):
            pg = pidx_ref[b * pps + i * ppcb + j]
            go(pltpu.make_async_copy(idx_hbm.at[li, 0, pg], buf.at[slot, j], sems.at[slot]))

        page(0)
        for j in range(1, ppcb):
            pl.when(j < held)(functools.partial(page, j))

    for t in range(nbuf - 1):

        @pl.when(t < num_items)
        def _warm(t=t):
            copies(t, lambda c: c.start())

    def item(t, carry):
        @pl.when(t + nbuf - 1 < num_items)
        def _prefetch():
            copies(t + nbuf - 1, lambda c: c.start())

        b, i, slot = item_slot_ref[t], item_block_ref[t], t % nbuf
        copies(t, lambda c: c.wait())
        keys = buf[slot].reshape(bk, d)
        dots = jax.lax.dot_general(q_ref[b], keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # [Hi, bk]
        score = jnp.sum(jnp.maximum(dots, 0.0) * w_ref[b], axis=0, keepdims=True)
        o_ref[pl.ds(b, 1), pl.ds(pl.multiple_of(i * bk, bk), bk)] = score
        return carry

    jax.lax.fori_loop(0, num_items, item, 0)


def paged_index_scores_stacked(
    q: jax.Array,  # [S, Hi, d]: the index's queries of this step's tokens
    w: jax.Array,  # [S, Hi] float32: the heads' weights
    pages: jax.Array,  # [n_layers, 1, N, psz, d]: the cached tokens' index keys
    layer: jax.Array,  # scalar int32
    lengths: jax.Array,  # i32 [S]
    page_indices: jax.Array,  # i32 [S, pages_per_sequence]
    *,
    pages_per_compute_block: int,
    schedule: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    interpret: bool = False,
) -> jax.Array:
    """I[s, t] = sum_j w[s, j] relu(q[s, j] . key of slot s's cached token
    t) in layer ``layer``: [S, pages_per_sequence * psz] float32, over the
    launch and the work list of ``paged_latent_attention_stacked`` (one item
    a live slot and block of pages that holds tokens of it, each page
    fetched once: 256 B a cached token at 128 bfloat16 values). Columns past
    a slot's length, and a slot of length 0, hold whatever was there: the
    caller's selection looks at cached tokens only."""
    S, Hi, d = q.shape
    n_layers, one, _, page_size, d_p = pages.shape
    pps = page_indices.shape[1]
    ppcb = pages_per_compute_block
    if one != 1 or d_p != d or d % 128:
        raise ValueError(f"index pages [layers, 1, N, psz, {d}] in whole lane tiles expected, got {list(pages.shape)}")
    if pps % ppcb:
        raise ValueError(f"pages_per_sequence={pps} not divisible by pages_per_compute_block={ppcb}")
    if schedule is None:
        schedule = decode_schedule(lengths, pps, page_size, ppcb)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    item_bytes = pages.dtype.itemsize
    vmem_bytes = _NBUF * ppcb * page_size * d * item_bytes + S * max(8, Hi) * (d * item_bytes + 128 * 4) + S * pps * page_size * 4
    return pl.pallas_call(
        functools.partial(_index_kernel, ppcb=ppcb, pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[vmem, vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            grid=(1,),
            scratch_shapes=(
                pltpu.VMEM((_NBUF, ppcb, page_size, d), pages.dtype),
                pltpu.SemaphoreType.DMA((_NBUF,)),
            ),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * vmem_bytes + (8 << 20)))),
        out_shape=jax.ShapeDtypeStruct((S, pps * page_size), jnp.float32),
        name="paged_index_scores",
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        page_indices.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *schedule,
        q.astype(pages.dtype),
        w.astype(jnp.float32)[:, :, None],
        pages,
    )
