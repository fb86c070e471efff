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
index, the page table and the work list as scalars (``DecodeItems``, the same
for every layer of a step), a ring of ``_NBUF`` VMEM buffers with two items'
copies in flight while one is computed, flash-style online softmax in f32.
What differs:

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

The work list is ``shared_decode_schedule()``'s (since PR 54): a block of
latent pages that several live slots' table rows name (a GRPO group's
siblings hold the first sample's prompt pages, ``SlotCache.alias``: three of
five block reads in the long-context cells) is ONE item, copied into VMEM once,
and its readers' query rows are stacked on the rows of one matmul a PASS, so
the block's tiles are loaded and pushed into the array once for them. A pass
costs by its rows (``pass_readers``): the array takes 128 rows for the pushes
that 32 need, but every [rows, 128] tile of a product is popped and summed on
the vector unit, so at 32 heads a pass of 2 readers costs what one reader's
item does (the block's copy bounds both), one of 4 readers 1.8 times that, and
one of 8 three times; an item's readers go through passes of 4 while more
than 2 are left and one of 2 for the rest. The launch walks the list in two
loops as ``paged_decode_attn`` does: shared items first (no mask: every
reader is past a shared block; an empty reader place of a pass merges into a
spare row past the slots), then the blocks of one slot each, masked by its
length, whose running state rides in the loop's carry (a slot's private items
are adjacent and ascending) and takes up what its shared items left in VMEM.
A slot meets its blocks in ascending order under either list and its last
block is never shared, so the merge order, the store and the arithmetic a row
sees (``m_new = max(m_prev, row max)``, probabilities rounded to the pages'
type before PV) are those of one item a (slot, block): on the chip the
outputs are the same bits (PERF.md, Findings, PR 54). The ring of copies runs
on across the two loops. The body binds ``jax.lax`` primitives only
(tests/test_paged_decode_budget.py holds its traced size, as the K/V
kernel's).

Where the layer has a learned index (DeepSeek-V3.2's: ONE index key a cached
token in a pool of its own, 128 lanes, on the same pages), a step first
scores every cached token of every live slot (``paged_index_scores_stacked``:
the same launch and work list over the index pool, [Hi, d] x [d, block] a
block, relu, the heads' weighted sum), the caller selects (models/hybrid.py
``select_top``), and the latent launch takes the selection as a mask
(``select``): every page that holds tokens is still fetched, the unselected
rows meet a probability of exactly 0. Both walk ``decode_schedule()``'s list,
one item a (slot, block): a shared block under a selection would need every
reader's own mask rows stacked, and the index's scores are a row a slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.paged_attention_q8 import (
    _MASK_VALUE,
    _NBUF,
    MAX_READERS,
    DecodeItems,
    _pick,
    _wide,
    decode_schedule,
    shared_decode_schedule,
)


def pass_readers(heads: int) -> tuple[int, int]:
    """(small, big): the readers of a shared block whose query rows ONE
    matmul takes. A pass costs by its rows (by the compiler's schedule for a
    v5e, bundles a pass of 32 / 64 / 128 / 256 rows over a block of 512 cached
    rows: 818 / 910 / 1,619 / 2,756, and the block's copy is about 900
    bundles' time), so the last one or two readers of an item go through a
    pass of 64 rows (2 readers at 32 heads) and more than that through passes
    of twice as many. Powers of two up to MAX_READERS, so a full item is
    whole passes; equal where one reader fills the big pass already."""
    small = min(MAX_READERS, 1 << max(0, (64 // heads).bit_length() - 1))
    return small, min(MAX_READERS, 2 * small)


def _latent_kernel(
    lengths_ref,  # SMEM [S] int32: valid tokens per slot
    pidx_ref,  # SMEM [S * pps] int32: flat page table
    layer_ref,  # SMEM [1] int32
    item_slot_ref,  # SMEM [S * pps / ppcb] int32: DecodeItems
    item_block_ref,
    count_ref,  # SMEM [2] int32
    next_ref,  # SMEM [S * pps / ppcb] int32
    q_ref,  # VMEM [S, H, lanes]: absorbed queries, in the pages' type
    *refs,  # with ``selected``: VMEM [S, pps * psz] int32, 1 where the slot attends to the cached token; then:
    # kv_hbm ANY [n_layers, 1, N, psz, lanes]; o_ref VMEM [S, H, value_lanes] f32; buf VMEM [_NBUF, ppcb, psz, lanes]; sems DMA [_NBUF];
    # and without ``selected``: a reader's running maximum and sum VMEM [S + 1, H, 1] f32 and accumulator [S + 1, H,
    # value_lanes] f32 between its shared items (row S takes a pass's empty reader places); a pass's stacked queries
    # VMEM [big * H, lanes]
    ppcb: int,
    pps: int,
    value_lanes: int,
    sm_scale: float,
    selected: bool,
    small: int,
    big: int,
):
    if selected:
        sel_ref, kv_hbm, o_ref, buf, sems = refs
    else:
        kv_hbm, o_ref, buf, sems, m_ref, l_ref, acc_ref, qs_ref = refs
    li = layer_ref[0]
    num_shared, num_items = count_ref[0], count_ref[1]
    max_items = item_slot_ref.shape[0]
    psz, lanes = kv_hbm.shape[-2:]
    nbuf = buf.shape[0]
    num_slots, H, _ = q_ref.shape
    bk = ppcb * psz
    nb = pps // ppcb

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)  # a slot no item names keeps these
    if ppcb > 1:
        # a slot's last block fetches only the pages that hold tokens and
        # computes over the whole block: what the other pages' buffers hold
        # meets a probability of exactly 0 and must be finite for that
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    def copies(t, go):
        """``go`` (``start`` or ``wait``) on item t's page copies, built
        identically both times; a buffer's copies share one semaphore (it
        counts bytes). An item past the list has no page: the condition of
        each page holds that too, so a trip needs no branch around its
        prefetch."""
        live = lax.lt(t, num_items)
        t = lax.min(t, max_items - 1)
        b, slot = item_slot_ref[t], lax.rem(t, nbuf)
        i = _pick(live, item_block_ref[t], 0)  # no table entry past the slot's row is read
        first_page = lax.add(lax.mul(b, pps), lax.mul(i, ppcb))
        held = _pick(live, lax.div(lax.add(lax.sub(lengths_ref[b], lax.mul(i, bk)), psz - 1), psz), 0)  # pages with tokens

        def page(j):
            pg = pidx_ref[lax.add(first_page, j)]
            go(pltpu.make_async_copy(kv_hbm.at[li, 0, pg], buf.at[slot, j], sems.at[slot]))

        for j in range(ppcb):
            pl.when(lax.gt(held, j))(functools.partial(page, j))

    def attend(slot, queries, seen, state):
        """The block in buffer ``slot`` under ``queries`` [n, lanes] (one
        slot's heads, or several readers' stacked), merged into their running
        ``state`` (maxima and sums [n, 1], accumulators [n, value_lanes]) as
        the flash-style online softmax does. ``seen`` [n, bk] says which
        cached tokens a row attends to, or is None where every row holds
        tokens in the whole block."""
        m_prev, l_prev, acc = state
        rows = lax.reshape(buf[slot], (bk, lanes))  # keys; their first value_lanes lanes the values
        logits = lax.dot_general(queries, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        logits = lax.mul(logits, sm_scale)  # [n, bk]
        if seen is not None:
            logits = _pick(seen, logits, _MASK_VALUE)
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(logits, (1,)), (1,)))
        p = lax.exp(lax.sub(logits, _wide(m_new, logits)))
        if selected and seen is not None:  # a block may hold no chosen token: the mask value is then its maximum, and exp(0) must not count
            p = _pick(seen, p, 0.0)
        corr = lax.exp(lax.sub(m_prev, m_new))
        l_new = lax.add(lax.mul(l_prev, corr), lax.expand_dims(lax.reduce_sum(p, (1,)), (1,)))
        pv = lax.dot_general(
            lax.convert_element_type(p, rows.dtype), lax.slice_in_dim(rows, 0, value_lanes, axis=1), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, lax.add(lax.mul(acc, _wide(corr, acc)), pv)

    lax.fori_loop(0, nbuf, lambda t, _: copies(t, start), None)  # fill the ring (rolled: traced once)
    fresh = (_MASK_VALUE, 0.0, 0.0)  # exp(_MASK_VALUE - m) is exactly 0

    def shared_item(t, _):
        """A block several slots read, fetched once: its readers' query rows
        stacked on the rows of ONE matmul a pass, ``big`` of them while more
        than ``small`` are left and ``small`` for the rest; every reader is
        past the block, so no mask by length. A reader's running state lives
        in VMEM between its items (they are not adjacent); a slot's first
        block starts it (what the scratch held may be anything)."""
        i, slot = item_block_ref[t], lax.rem(t, nbuf)
        first = lax.eq(i, 0)
        copies(t, wait)

        def after(b):  # the next reader of the block, -1 past the last
            return _pick(lax.ge(b, 0), next_ref[lax.add(lax.mul(lax.max(b, 0), nb), i)], -1)

        def a_pass(n, b, done):
            at = []
            for _r in range(n):  # the chain of readers; an empty place merges into the row past the slots
                at.append(_pick(lax.ge(b, 0), b, num_slots))
                b = after(b)
            for r in range(n):
                qs_ref[r * H : (r + 1) * H, :] = q_ref[lax.min(at[r], num_slots - 1)]
            state = (lax.concatenate([ref[r] for r in at], 0) if n > 1 else ref[at[0]] for ref in (m_ref, l_ref, acc_ref))
            new = attend(slot, qs_ref[: n * H, :], None, tuple(_pick(first, x0, x) for x0, x in zip(fresh, state)))
            for ref, x in zip((m_ref, l_ref, acc_ref), new):
                for r in range(n):
                    ref[at[r]] = lax.slice_in_dim(x, r * H, (r + 1) * H, axis=0) if n > 1 else x
            return b, lax.add(done, n)

        def passes(carry):
            b, done = carry
            if big == small:
                return a_pass(small, b, done)
            further = b
            for _r in range(small):
                further = after(further)
            more = lax.bitwise_and(lax.ge(further, 0), lax.le(lax.add(done, big), MAX_READERS))  # over ``small`` are left
            return lax.cond(more, functools.partial(a_pass, big), functools.partial(a_pass, small), b, done)

        lax.while_loop(lambda c: lax.bitwise_and(lax.ge(c[0], 0), lax.lt(c[1], MAX_READERS)), passes, (item_slot_ref[t], jnp.int32(0)))
        copies(lax.add(t, nbuf), start)  # into the buffer this item has just left: two items stay in flight
        return _

    def private_item(t, carry):
        """A block of one slot, masked by its length (and by ``select``). A
        slot's private items are adjacent and ascending, so its running state
        rides in the loop's carry; where its earlier blocks were shared items
        the first one takes up what they left in VMEM."""
        b, i, slot = item_slot_ref[t], item_block_ref[t], lax.rem(t, nbuf)
        length = lengths_ref[b]
        col = lax.add(lax.mul(i, bk), lax.broadcasted_iota(jnp.int32, (H, bk), 1))
        seen = lax.lt(col, length)
        if selected:  # the tokens the slot's index chose: the others are fetched with their page and masked
            chosen = sel_ref[pl.ds(b, 1), pl.ds(pl.multiple_of(lax.mul(i, bk), bk), bk)]
            seen = lax.bitwise_and(seen, _wide(lax.ne(chosen, 0), seen))
        first = lax.eq(i, 0)
        if not selected:  # the slot's first private item, its earlier blocks shared items: a branch, taken once a slot at most
            taken_up = lax.bitwise_or(lax.eq(t, num_shared), lax.ne(item_slot_ref[lax.max(lax.sub(t, 1), 0)], b))
            carry = lax.cond(
                lax.bitwise_and(taken_up, lax.gt(i, 0)), lambda: (m_ref[b], l_ref[b], acc_ref[b]), lambda: carry
            )
        copies(t, wait)
        m_new, l_new, acc = attend(slot, q_ref[b], seen, tuple(_pick(first, x0, x) for x0, x in zip(fresh, carry)))

        @pl.when(lax.eq(i, lax.sub(lax.div(lax.add(length, bk - 1), bk), 1)))
        def _store():  # the slot's last block, never a shared one
            o_ref[b] = lax.div(acc, _wide(l_new, acc))

        copies(lax.add(t, nbuf), start)
        return m_new, l_new, acc

    # the ring of copies runs on across the two loops
    if not selected:
        lax.fori_loop(0, num_shared, shared_item, None)
    init = (jnp.full((H, 1), _MASK_VALUE, jnp.float32), jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, value_lanes), jnp.float32))
    lax.fori_loop(num_shared, num_items, private_item, init)


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
    schedule: DecodeItems | tuple[jax.Array, jax.Array, jax.Array] | None = None,
    select: jax.Array | None = None,  # bool [S, pages_per_sequence * psz]: the cached tokens each slot attends to
    interpret: bool = False,
) -> jax.Array:
    """sum_s softmax_s(q . row_s * sm_scale) row_s[:value_lanes] over each
    slot's cached rows of layer ``layer``: [S, H, value_lanes] float32. A
    slot of length 0 costs nothing and returns exact zeros. ``schedule`` is
    ``shared_decode_schedule()``'s list of the same lengths, table and block
    size (or ``decode_schedule()``'s, where nothing is to be shared);
    computed here otherwise. With ``select`` the softmax and the sum run
    over the selected tokens only (every slot with tokens must select one):
    the MASKED form of a sparse read, every page that holds tokens still
    fetched, and the list is one that shares nothing
    (``DecodeItems.private``: a shared block would need every reader's mask
    rows stacked, and the launch does not walk shared items then)."""
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
    if schedule is None and select is None:
        schedule, _ = shared_decode_schedule(lengths, page_indices, page_size, ppcb)
    elif schedule is None:
        schedule = decode_schedule(lengths, pps, page_size, ppcb)
    if not isinstance(schedule, DecodeItems):
        schedule = DecodeItems.private(schedule)
    max_items = S * (pps // ppcb)
    if schedule[0].shape != (max_items,):
        raise ValueError(f"schedule of {schedule[0].shape[0]} items, {max_items} expected")
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    item_bytes = pages.dtype.itemsize
    small, big = pass_readers(H)
    f32 = jnp.float32
    # the ring, the queries and the output
    vmem_bytes = _NBUF * ppcb * page_size * lanes * item_bytes + S * max(8, H) * (lanes * item_bytes + value_lanes * 4)
    scratch = [pltpu.VMEM((_NBUF, ppcb, page_size, lanes), pages.dtype), pltpu.SemaphoreType.DMA((_NBUF,))]
    if select is None:  # a reader's running state between its shared items (an [H, 1] column pads to 128 lanes), a pass's queries
        scratch += [pltpu.VMEM((S + 1, H, 1), f32)] * 2 + [pltpu.VMEM((S + 1, H, value_lanes), f32), pltpu.VMEM((big * H, lanes), pages.dtype)]
        vmem_bytes += (S + 1) * max(8, H) * (value_lanes + 2 * 128) * 4
    chosen = () if select is None else (select.astype(jnp.int32),)
    if chosen and select.shape != (S, pps * page_size):
        raise ValueError(f"select {list(select.shape)} for {S} slots of {pps} pages of {page_size}")
    vmem_bytes += sum(4 * c.size for c in chosen)
    return pl.pallas_call(
        functools.partial(
            _latent_kernel, ppcb=ppcb, pps=pps, value_lanes=value_lanes, sm_scale=float(sm_scale), selected=bool(chosen), small=small, big=big
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            in_specs=[vmem] * (1 + len(chosen)) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            grid=(1,),
            scratch_shapes=tuple(scratch),
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
