"""Paged decode attention over the FULL stacked KV cache: one repo-native
Pallas kernel for bf16 and int8/fp8 pages.

``paged_attention_stacked`` takes the stacked cache [n_layers, KH, N, psz,
hd] plus a (traced) layer index delivered via scalar prefetch, and slices
``ref.at[li]`` INSIDE the kernel. A pallas operand must be a real buffer,
so feeding the kernel a ``dynamic_index_in_dim`` layer slice from the
layers scan makes XLA materialize a copy of every layer's pages every
step: full-cache read+write traffic per decode step. In-kernel slicing
DMAs only the pages attention actually reads.

The launch is one kernel invocation with no grid to walk. Its work list
(``shared_decode_schedule``: each DISTINCT block of
``pages_per_compute_block`` pages that holds tokens of a live slot, once) is
the same for every layer, so a decode step computes it once and hands it to
each layer's launch by scalar prefetch; a slot of length 0 is in no item,
costs nothing and returns exact zeros. An item covers EVERY KV head of its
block: ONE copy a page and pool moves that page of all the heads (a strided
window ``[KH, psz, hd]`` of the pool) into one of three VMEM buffers. The loop
body is instruction issue before it is anything else (PERF.md, Findings, PR
41: the scalar core builds a copy's descriptor and its bounds checks in about
21 VLIW bundles, and with a copy a head they were half of the body's 711 at 2
KV heads and more at 4 and 30; the launch took bundles x items at 0.9 GHz, not
its bytes' time). While item t is computed the copies of items t+1 and t+2
are in flight — across slot boundaries, so no item but the first starts on a
cold buffer — and item t+3's start at the end of item t's trip, into the
buffer it has just left (measured on the v5e: the third buffer is worth
5-10%, a fourth nothing; started at the end of the trip one item less ahead,
the launch is 15% slower). A slot's last block fetches only the pages that
hold tokens.

A block that several live slots' table rows name (a GRPO group's siblings
hold the first sample's prompt pages, ``SlotCache.alias``; three of five
block reads in the long-context cell) is ONE item: it is copied into VMEM
once and the readers' query rows, up to MAX_READERS slots', are stacked on
the rows of one matmul a head, so K and V tiles are pushed into the array
once too (PERF.md, Findings, PR 45: 1,370 -> 313 us a launch at that cell's
shape). What is shared is read off the table and the lengths, nothing else
(``shared_decode_schedule``). The list holds the shared items first,
block-major, then every other block slot-major, and the launch walks them in
two loops whose per-head code is the same function in two shapes ([G, bk]
for one slot's item, masked by its length; [readers x G, bk] unmasked, every
reader being past a shared block): an item of one slot pays for no row it
does not have. The ring of copies runs on across the two loops.

A trip is ONE basic block but for the copies' page conditions (every branch
less is instructions less, and the compiler schedules within a block): the
waits first; then QK of every head, and only then, head by head, the softmax
and PV (alternating K's transposed and V's plain pushes into the MXU head by
head costs 3% on bf16 pages and 20-40% on int8 at the same bundle count);
then the prefetch, whose page conditions also say "the item exists". Each
item computes its block's OWN flash-style statistics in f32 (the block's
maximum, sum and accumulator, no read of a slot's running state) and merges
them, every head's at once, into the slot's running state by the usual
two-term rescale, started at the slot's first block. The running state lives
in VMEM scratch, a row a slot (a slot's items are no longer adjacent: its shared blocks come in the
first loop); a slot meets its blocks in ascending order under either list, so
the merge order is the one-item-a-slot launch's. Every item of one slot
stores the normalised output, and the slot's last block, never shared, stores
last: no branch for it. Two items a trip, and QK of item t+1 under item t's
softmax, were built and measured and are not here: no fewer bundles an item,
and slower on the chip.

The launch's TRACED size is held under a budget
(tests/test_paged_decode_budget.py): a start-up traces the chunk program's
launch sites on a host core that takes a millisecond and more for every
jitted jnp function called inside, an operator of a traced value among them
(PR 44's form of this kernel was refused for 12 s a site of set-up at 30 KV
heads, and this one's first draft cost 5 s a site at 1.5 times the
equations). So the body binds ``jax.lax`` primitives and nothing else, reads
and writes a slot's running state once an item for every head together, and
keeps under the loop over KV heads only what a head must have: its two tile
loads, its two matmuls and its softmax.

Both matmuls take f32 operands (pages and queries upcast in VMEM; the
softmax scale and the K scale row multiply the f32 logits). Measured on the
v5e at both benchmark shapes (PERF.md, Findings, PR 25): bf16 operands with
f32 accumulation — the probabilities as one bf16 term or as two stacked on
the rows — take the same time to within 4%: with 6-8 query rows a head the
matmuls are weight loads (every K and V tile is pushed into the array once)
and the launch was bound by its instructions then and is by its copies now,
not by the array; so the kernel keeps the one form that is exact for every
page dtype.

The body is written here rather than taken from jax's library kernel
(jax.experimental.pallas.ops.tpu.paged_attention) because that one cannot
serve quantized pages on the chip:

  - scales are stored LANE-MAJOR, [n_layers, KH, N, 1, psz] (one f32 per
    token vector, the page's tokens along the lanes). A trailing-1 layout
    ([..., psz, 1]) pads every scale to a full 128-lane row in HBM and
    cannot be DMA-sliced on the TPU ("slice shape along dimension must be
    aligned to tiling (128)"); the library wrapper instead widens scales
    to head_dim, which INVERTS the halved-HBM premise of int8 KV.
  - with tokens along the lanes no in-VMEM relayout is needed: K's scale
    multiplies the logits' columns (q.k_int * s_k) and V's scale the
    probabilities' columns ((p * s_v) @ v_int) — G*T multiplies per block
    instead of T*hd.

int8 and float8_e4m3fn pages share one formula: both store
``x * 127.5 / scale`` (inference/paged_kv.py quantize_kv).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_INT8 = 127.5
_MASK_VALUE = -1e30
# blocks of pages in VMEM at once: one computed on, two in flight (measured
# on the v5e: a third buffer is worth 5-10%, a fourth nothing)
_NBUF = 3
# slots whose query rows one item's matmuls take: a GRPO group of 8 is one item a shared block, 16 two
MAX_READERS = 8


def paged_kernel_ok(head_dim: int, page_size: int, quant: bool) -> bool:
    """Static shape rule of the COMPILED paged kernels (this module's decode
    kernel and ops/paged_suffix_attention.py; interpret mode takes any
    shape): a page's [page_size, head_dim] slice and, under quantization,
    its [1, page_size] scale row must be DMA-sliceable — lane-aligned
    (what the chip's compiler answered for a described v5e: head_dim 64
    and quantized 32/64-token pages are refused, bf16 pages of 8 tokens
    and up are taken). Engines outside it take the gather path."""
    return head_dim % 128 == 0 and page_size % (128 if quant else 8) == 0


def decode_schedule(
    lengths: jax.Array,  # i32 [S]
    pages_per_sequence: int,
    page_size: int,
    pages_per_compute_block: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The work list of a launch that fetches every slot's blocks for that
    slot alone (ops/paged_latent_attention.py's index launch, and its latent
    launch where it reads under the index's selection; this module's over a
    table that aliases nothing, a window layer's rings), the same for every
    layer of a decode step (so a step computes it once and hands it to each
    launch): one item per (live slot, block of its tokens), slot-major.
    Returns (slot of item t, block of item t, [number of items]); entries
    past the count are never read. ``DecodeItems.private`` puts it in the
    form the attention launches walk."""
    ppcb = pages_per_compute_block
    bk = ppcb * page_size
    num_slots = lengths.shape[0]
    nblk = (lengths.astype(jnp.int32) + bk - 1) // bk
    end = jnp.cumsum(nblk)
    t = jnp.arange(num_slots * (pages_per_sequence // ppcb), dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(t[:, None] >= end[None, :], axis=1), num_slots - 1)
    block = t - (end - nblk)[slot]
    return slot, block, end[-1:]


class DecodeItems(NamedTuple):
    """The attention launches' work list (``paged_decode_attn``, and
    ``paged_latent_attn`` of ops/paged_latent_attention.py): each DISTINCT
    block of pages once.
    Items [0, count[0]) are blocks that several slots read (block-major);
    items [count[0], count[1]) are blocks of one slot, slot-major."""

    slot: jax.Array  # i32 [max_items]: the slot whose table row names the item's pages; its first reader
    block: jax.Array  # i32 [max_items]
    count: jax.Array  # i32 [2]: (shared items, all items)
    next_reader: jax.Array  # i32 [S * blocks a row], at slot * blocks + block: the next slot reading it, or -1

    @classmethod
    def private(cls, schedule: tuple[jax.Array, jax.Array, jax.Array]) -> "DecodeItems":
        """``decode_schedule()``'s list as it is: nothing is shared."""
        slot, block, n = schedule
        count = jnp.concatenate([jnp.zeros(1, jnp.int32), n.astype(jnp.int32)])
        return cls(slot, block, count, jnp.full((slot.shape[0],), -1, jnp.int32))


class DecodeFetch(NamedTuple):
    """What a launch over ``shared_decode_schedule()``'s list fetches, a layer."""

    tokens: jax.Array  # i32: cached tokens in the items' blocks (the distinct tokens of the live slots)
    blocks_listed: jax.Array  # i32: blocks the live slots' rows hold tokens in (what one item a slot fetches)
    blocks: jax.Array  # i32: items

    def counted(self, counts: dict) -> dict:
        """``counts`` with a decode chunk's two block counts (the models'
        ``count_shapes`` leaves), where it carries them, advanced by this step's."""
        if "attn_blocks_listed" not in counts:
            return counts
        return {
            **counts,
            "attn_blocks_listed": counts["attn_blocks_listed"] + self.blocks_listed,
            "attn_blocks_fetched": counts["attn_blocks_fetched"] + self.blocks,
        }


def shared_decode_schedule(
    lengths: jax.Array,  # i32 [S]
    page_table: jax.Array,  # i32 [S, pages_per_sequence]
    page_size: int,
    pages_per_compute_block: int,
) -> tuple[DecodeItems, DecodeFetch]:
    """The launch's work list over a table whose rows may alias pages (a GRPO
    group's siblings hold the prompt's pages of the first, a prefix-cache hit
    the cached ones), the same for every layer of a decode step. Slots b and
    b' share block i when the ``ppcb`` entries of their rows for every block
    0..i are the same physical pages and both hold tokens PAST block i (so
    every page of it is full for both, and each slot's last block, where its
    output is stored, stays its own): what the table and the lengths show,
    nothing else. Slots sharing a block are chained in slot order
    (``next_reader``), MAX_READERS of them an item; every other block with
    tokens is an item of its own slot, in ``decode_schedule()``'s order, so a
    table that aliases nothing gives that list. A slot's blocks are met in
    ascending order: its shared ones (a prefix of its row) block-major
    before any private one."""
    ppcb = pages_per_compute_block
    bk = ppcb * page_size
    S, pps = page_table.shape
    nb = pps // ppcb
    max_items = S * nb
    lengths = lengths.astype(jnp.int32)
    nblk = (lengths + bk - 1) // bk  # [S]
    slots = jnp.arange(S, dtype=jnp.int32)
    # [nb, S, S]: rows b and b' name the same pages in block i and in every block before it, both past it
    pages = page_table.astype(jnp.int32).reshape(S, nb, ppcb).transpose(1, 2, 0)  # [nb, ppcb, S]
    blocks = jnp.arange(nb, dtype=jnp.int32)
    inner = blocks[:, None] < nblk[None, :] - 1  # [nb, S]
    same = jnp.all(pages[:, :, :, None] == pages[:, :, None, :], axis=1) & inner[:, :, None] & inner[:, None, :]
    differ = jnp.min(jnp.where(same, nb, blocks[:, None, None]), axis=0)  # [S, S] the first block they do not share
    same = blocks[:, None, None] < differ[None]
    later = slots[None, :] > slots[:, None]  # [b, b']: b' > b
    shared = jnp.sum(same, axis=2) > 1  # [nb, S]; ``same`` holds b with itself wherever b is past the block
    rank = jnp.sum(same & later.T[None], axis=2)  # readers of the block before b
    lead = shared & (rank % MAX_READERS == 0)  # b is the first reader of an item
    nxt = same & later[None]
    next_reader = jnp.where(jnp.any(nxt, axis=2), jnp.argmax(nxt, axis=2), -1).astype(jnp.int32)  # [nb, S]
    # the shared items, block-major: item t is the t-th leader of the flat [nb, S]; a running count by two small
    # matrix products (exact: counts under 2^24), not a scan over S * nb elements
    f32 = jnp.float32
    exact = dict(precision=jax.lax.Precision.HIGHEST)
    upto = jnp.dot(lead.astype(f32), (slots[:, None] <= slots[None, :]).astype(f32), **exact)  # [nb, S] leaders of the block up to b
    before = jnp.dot((blocks[None, :] < blocks[:, None]).astype(f32), upto[:, -1], **exact)  # [nb] leaders of the blocks before
    lead_upto = (before[:, None] + upto).astype(jnp.int32).reshape(-1)
    n_shared = lead_upto[-1]
    t = jnp.arange(max_items, dtype=jnp.int32)
    at = jnp.sum(lead_upto[None, :] <= t[: max_items // 2, None], axis=1, dtype=jnp.int32)
    at = jnp.minimum(jnp.pad(at, (0, max_items - max_items // 2)), max_items - 1)
    # the others, as decode_schedule() lists them: a slot's blocks past its shared prefix
    held = jnp.sum(shared, axis=0, dtype=jnp.int32)  # [S] shared blocks of a slot: blocks 0..held-1
    own = nblk - held
    end = jnp.sum(jnp.where(slots[None, :] <= slots[:, None], own[None, :], 0), axis=1)  # [S] running count
    u = t - n_shared
    past = u[:, None] >= end[None, :]  # [items, S]: item u lies past slot b's
    slot = jnp.minimum(jnp.sum(past, axis=1, dtype=jnp.int32), S - 1)
    # block = held[slot] + u - (end - own)[slot], the slot's term summed up from its steps over the slots before
    # (no gather: XLA's is a chain of selects a source element)
    first = end - own - held
    block = u - first[0] - jnp.sum(jnp.where(past[:, : S - 1], (first[1:] - first[:-1])[None, :], 0), axis=1)
    is_shared = t < n_shared
    items = DecodeItems(
        jnp.where(is_shared, at % S, slot),
        jnp.where(is_shared, at // S, block),
        jnp.stack([n_shared, n_shared + jnp.sum(own)]),
        next_reader.T.reshape(-1),
    )
    tokens = n_shared * bk + jnp.sum(jnp.where(nblk > 0, lengths - held * bk, 0), dtype=jnp.int32)
    return items, DecodeFetch(tokens, jnp.sum(nblk, dtype=jnp.int32), items.count[1])


def live_order(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(slot indices with the live ones first, how many are live) of a [S]
    bool mask: the work list of the kernels that visit a slot once
    (ops/paged_kv_write.py, ops/ssm_state_update.py), the same for every
    layer of a step."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live).astype(jnp.int32)


# The kernel's body binds ``jax.lax`` primitives and never a jnp function or an operator of a traced value: each of
# those (``a * b``, ``jnp.where``, ``x.astype``, ``%``) is a call of a jitted function, which jax traces on its own, a
# millisecond and more of a start-up's host time apiece, and the body holds some of them a KV head (PERF.md,
# Findings, PR 45: the chunk program's first call took 21.6 s longer at 30 KV heads with jnp's operators here).


def _pick(pred, x, y):
    """``jnp.where`` as one ``select_n``: ``x`` or ``y`` may be a Python
    number beside an array; ``pred`` a scalar or of the array's shape."""
    if not isinstance(x, jax.Array):
        x = lax.full_like(y, x)
    elif not isinstance(y, jax.Array):
        y = lax.full_like(x, y)
    return lax.select(pred, x, y)


def _wide(col, like):
    """A [..., 1] column (or a [1, n] row) over the shape of ``like``: the
    broadcast jnp's operators make unseen."""
    return lax.broadcast_in_dim(col, like.shape, tuple(range(like.ndim)))


def _f32(x):
    return lax.convert_element_type(x, jnp.float32)


def reader_rows(group: int) -> int:
    """Rows a reader's ``group`` query rows take in a shared item's stacked
    matmul: its own where they pack a tile of 8 sublanes evenly, else a whole
    number of tiles (so no reader's rows straddle one)."""
    return group if group in (1, 2, 4) else -(-group // 8) * 8


def _decode_kernel(
    lengths_ref,  # SMEM [S] int32 — valid tokens per slot
    pidx_ref,  # SMEM [S * pps] int32 — flat page table
    layer_ref,  # SMEM [1] int32 — which layer's pages to read
    item_slot_ref,  # SMEM [S * pps / ppcb] int32 — DecodeItems
    item_block_ref,
    count_ref,  # SMEM [2] int32
    next_ref,  # SMEM [S * pps / ppcb] int32
    q_ref,  # VMEM [S, KH, G, hd] — raw queries (``sm_scale`` is applied here)
    *refs,
    ppcb: int,
    pps: int,
    quant: bool,
    sm_scale: float,
):
    if quant:
        k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, k_buf, ks_buf, v_buf, vs_buf, sems, *state = refs
        pools = ((k_hbm, k_buf), (ks_hbm, ks_buf), (v_hbm, v_buf), (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *state = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
        ks_buf = vs_buf = None
    # a slot's running (maximum, sum, accumulator), one row more than slots (where a shared item's empty reader
    # places merge); a shared item's stacked queries and the block's statistics of every head
    m_ref, l_ref, acc_ref, qs_ref, m_blk_ref, l_blk_ref, acc_blk_ref = state
    li = layer_ref[0]
    num_shared, num_items = count_ref[0], count_ref[1]
    max_items = item_slot_ref.shape[0]
    _, num_kv_heads, _, psz, hd = k_hbm.shape
    nbuf = k_buf.shape[0]
    num_slots, _, G, _ = q_ref.shape
    bk = ppcb * psz  # tokens per compute block
    nb = pps // ppcb
    rows = reader_rows(G)

    # a slot no item names (length 0) keeps these zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    qs_ref[...] = jnp.zeros(qs_ref.shape, qs_ref.dtype)  # rows no reader fills must be finite
    if ppcb > 1:
        # a slot's last block fetches only the pages that hold tokens and
        # computes over the whole block: what the other pages' buffers hold
        # meets a probability of exactly 0 and must be finite for that
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        if quant:
            vs_buf[...] = jnp.zeros(vs_buf.shape, vs_buf.dtype)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    def copies(t, go):
        """Apply ``go`` (``start`` or ``wait``) to item t's copies — built
        identically both times: ONE copy a page and pool, a strided window
        over every KV head, all on the buffer's semaphore (it counts bytes).
        An item past the list has no page: the condition of each page holds
        that too, so the loop body needs no branch around its prefetch."""
        live = lax.lt(t, num_items)
        t = lax.min(t, max_items - 1)
        b, buf = item_slot_ref[t], lax.rem(t, nbuf)
        i = _pick(live, item_block_ref[t], 0)  # no table entry past the slot's row is read
        first_page = lax.add(lax.mul(b, pps), lax.mul(i, ppcb))
        # pages with tokens
        held = _pick(live, lax.div(lax.add(lax.sub(lengths_ref[b], lax.mul(i, bk)), psz - 1), psz), 0)

        def page(j):
            pg = pidx_ref[lax.add(first_page, j)]
            for hbm, vmem in pools:
                go(pltpu.make_async_copy(hbm.at[li, :, pg], vmem.at[buf, :, j], sems.at[buf]))

        for j in range(ppcb):
            pl.when(lax.gt(held, j))(functools.partial(page, j))

    def scale_row(buf_ref, buf, h):
        # [ppcb, 1, psz] -> [1, bk]: the pages' lane-major scales side by side
        s = _f32(buf_ref[buf, h])
        return lax.div(lax.concatenate([lax.index_in_dim(s, j, 0, keepdims=False) for j in range(ppcb)], 1), _MAX_INT8)

    def heads(buf, queries, valid):
        """The per-head code, over the block in buffer ``buf``: QK of every
        head, and only then, head by head, the softmax and PV. ``queries``
        [KH, n, hd] f32 holds every head's query rows (one slot's, or several
        readers' stacked); ``valid`` [n, bk] masks the logits, or is None
        where every row holds tokens in the whole block. Returns the block's
        OWN statistics (against its own maximum: nothing here reads a slot's
        running state), every head's stacked: maxima and sums [KH, n, 1],
        accumulators [KH, n, hd]."""
        logits = []
        for h in range(num_kv_heads):
            k = lax.reshape(_f32(k_buf[buf, h]), (bk, hd))
            q = lax.index_in_dim(queries, h, 0, keepdims=False)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # [n, bk]
            s = lax.mul(s, sm_scale)
            if quant:
                s = lax.mul(s, _wide(scale_row(ks_buf, buf, h), s))
            logits.append(s if valid is None else _pick(valid, s, _MASK_VALUE))
        stats = []
        for h in range(num_kv_heads):
            m_blk = lax.expand_dims(lax.reduce_max(logits[h], (1,)), (1,))  # an item holds a valid token
            p = lax.exp(lax.sub(logits[h], _wide(m_blk, logits[h])))
            l_blk = lax.expand_dims(lax.reduce_sum(p, (1,)), (1,))
            if quant:
                p = lax.mul(p, _wide(scale_row(vs_buf, buf, h), p))
            v = lax.reshape(_f32(v_buf[buf, h]), (bk, hd))
            acc_blk = lax.dot_general(p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            stats.append((m_blk, l_blk, acc_blk))
        # a head's [n, .] under a leading axis of heads: no data moves, the tiles are a head's already
        return tuple(lax.concatenate([lax.expand_dims(x, (0,)) for x in part], 0) for part in zip(*stats))

    def merged(first, at, m_blk, l_blk, acc_blk):
        """The block's statistics of every head [KH, G, .] merged into slot
        ``at``'s running state by the two-term rescale; the state is stored,
        (sum, accumulator) returned."""
        # a slot's first block starts it (what the scratch held may be anything)
        m_prev = _pick(first, _MASK_VALUE, m_ref[at])  # exp(_MASK_VALUE - m) is exactly 0
        l_prev = _pick(first, 0.0, l_ref[at])
        acc_prev = _pick(first, 0.0, acc_ref[at])
        m_new = lax.max(m_prev, m_blk)
        a = lax.exp(lax.sub(m_prev, m_new))
        c = lax.exp(lax.sub(m_blk, m_new))
        l_new = lax.add(lax.mul(l_prev, a), lax.mul(l_blk, c))
        acc_new = lax.add(lax.mul(acc_prev, _wide(a, acc_prev)), lax.mul(acc_blk, _wide(c, acc_blk)))
        m_ref[at], l_ref[at], acc_ref[at] = m_new, l_new, acc_new
        return l_new, acc_new

    lax.fori_loop(0, nbuf, lambda t, _: copies(t, start), None)  # fill the ring (rolled: traced once)

    def shared_item(t, _):
        """A block several slots read: their query rows stacked on the rows
        of ONE matmul a head (K and V tiles are pushed into the array once),
        every reader past the block, so no mask."""
        i, buf = item_block_ref[t], lax.rem(t, nbuf)
        b, at = item_slot_ref[t], []
        for r in range(MAX_READERS):  # the chain of readers; an empty place merges into the row past the slots
            at.append(_pick(lax.ge(b, 0), b, num_slots))
            if r + 1 < MAX_READERS:
                b = _pick(lax.ge(b, 0), next_ref[lax.add(lax.mul(lax.max(b, 0), nb), i)], -1)
        for r in range(MAX_READERS):
            qs_ref[:, r * rows : r * rows + G, :] = _f32(q_ref[lax.min(at[r], num_slots - 1)])
        copies(t, wait)
        m_blk_ref[...], l_blk_ref[...], acc_blk_ref[...] = heads(buf, qs_ref[...], None)
        first = lax.eq(i, 0)
        for r in range(MAX_READERS):  # a reader's rows of every head
            mine = (slice(None), slice(r * rows, r * rows + G))
            merged(first, at[r], m_blk_ref[mine], l_blk_ref[mine], acc_blk_ref[mine])
        copies(lax.add(t, nbuf), start)  # into the buffer this item has just left: two items stay in flight
        return _

    def private_item(t, _):
        b, i, buf = item_slot_ref[t], item_block_ref[t], lax.rem(t, nbuf)
        col = lax.add(lax.mul(i, bk), lax.broadcasted_iota(jnp.int32, (G, bk), 1))
        valid = lax.lt(col, lengths_ref[b])
        first = lax.eq(i, 0)
        copies(t, wait)
        l_new, acc = merged(first, b, *heads(buf, _f32(q_ref[b]), valid))
        # normalised at every block: the slot's last one, never shared, stores last
        o_ref[b] = lax.div(acc, _wide(l_new, acc))
        copies(lax.add(t, nbuf), start)
        return _

    # each trip ONE basic block but for the copies' page conditions; the ring runs on across the two loops
    lax.fori_loop(0, num_shared, shared_item, None)
    lax.fori_loop(num_shared, num_items, private_item, None)


def paged_attention_q8(
    q: jax.Array,  # [S, H, hd] — RAW (scaling applied internally)
    k_pages: jax.Array,  # int8/fp8 [KH, N, psz, hd]
    k_scales: jax.Array,  # f32 [KH, N, 1, psz]
    v_pages: jax.Array,
    v_scales: jax.Array,
    lengths: jax.Array,  # i32 [S]
    page_indices: jax.Array,  # i32 [S, pages_per_sequence]
    *,
    pages_per_compute_block: int,
    interpret: bool = False,
) -> jax.Array:
    """Single-layer quantized entry: the stacked launch with a leading
    layer axis of 1 (one launch path to maintain)."""
    return paged_attention_stacked(
        q,
        k_pages[None],
        v_pages[None],
        jnp.int32(0),
        lengths,
        page_indices,
        pages_per_compute_block=pages_per_compute_block,
        k_scales=k_scales[None],
        v_scales=v_scales[None],
        interpret=interpret,
    )


def paged_attention_stacked(
    q: jax.Array,  # [S, H, hd] — RAW (the kernel applies ``sm_scale``)
    k_pages: jax.Array,  # [n_layers, KH, N, psz, hd] (bf16, int8 or fp8)
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    lengths: jax.Array,  # i32 [S]
    page_indices: jax.Array,  # i32 [S, pages_per_sequence]
    *,
    pages_per_compute_block: int,
    schedule: DecodeItems | tuple[jax.Array, jax.Array, jax.Array] | None = None,
    k_scales: jax.Array | None = None,  # f32 [n_layers, KH, N, 1, psz]
    v_scales: jax.Array | None = None,
    sm_scale: float | None = None,  # softmax scale; default 1/sqrt(hd)
    interpret: bool = False,
) -> jax.Array:
    """Paged attention reading layer ``layer`` of the FULL stacked cache —
    zero layer-slice copies (see module docstring). Scales, when given,
    are lane-major ([..., 1, psz]) end to end. ``schedule`` is
    ``shared_decode_schedule()``'s list of the same lengths, table and block
    size, for a caller that launches once per layer (or ``decode_schedule()``'s
    of the same lengths, table width and block size, where the table aliases
    nothing); computed here otherwise."""
    batch_size, num_q_heads, head_dim = q.shape
    n_layers, num_kv_heads, _, page_size, head_dim_k = k_pages.shape
    _, pages_per_sequence = page_indices.shape
    ppcb = pages_per_compute_block
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k/v page shapes differ: {k_pages.shape} {v_pages.shape}")
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")
    if quant and k_scales.shape != (*k_pages.shape[:-2], 1, page_size):
        raise ValueError(
            f"lane-major scales [..., 1, {page_size}] expected, got {k_scales.shape}"
        )
    if num_q_heads % num_kv_heads:
        raise ValueError(f"H={num_q_heads} not divisible by KH={num_kv_heads}")
    if head_dim_k != head_dim:
        raise ValueError(f"head_dim mismatch {head_dim} vs {head_dim_k}")
    if pages_per_sequence % ppcb:
        raise ValueError(
            f"pages_per_sequence={pages_per_sequence} not divisible by "
            f"pages_per_compute_block={ppcb}"
        )
    if schedule is None:
        schedule, _ = shared_decode_schedule(lengths, page_indices, page_size, ppcb)
    elif not isinstance(schedule, DecodeItems):
        schedule = DecodeItems.private(schedule)
    max_items = batch_size * (pages_per_sequence // ppcb)
    if schedule[0].shape != (max_items,):
        raise ValueError(
            f"schedule of {schedule[0].shape[0]} items, {max_items} expected for "
            f"{batch_size} slots x {pages_per_sequence // ppcb} blocks"
        )

    G = num_q_heads // num_kv_heads
    # [S, H, hd] -> [S, KH, G, hd] is a free reshape
    qg = q.reshape(batch_size, num_kv_heads, G, head_dim)
    vmem_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def page_buf(dtype):
        return pltpu.VMEM((_NBUF, num_kv_heads, ppcb, page_size, head_dim), dtype)

    def scale_buf(dtype):
        return pltpu.VMEM((_NBUF, num_kv_heads, ppcb, 1, page_size), dtype)

    if quant:
        pages = [k_pages, k_scales, v_pages, v_scales]
        scratch = [
            page_buf(k_pages.dtype), scale_buf(k_scales.dtype),
            page_buf(v_pages.dtype), scale_buf(v_scales.dtype),
        ]
    else:
        pages = [k_pages, v_pages]
        scratch = [page_buf(k_pages.dtype), page_buf(v_pages.dtype)]
    scratch.append(pltpu.SemaphoreType.DMA((_NBUF,)))  # one per buffer
    # the slots' running state (a row past the slots for a shared item's empty places), then a shared item's
    # stacked queries and block statistics
    stacked = MAX_READERS * reader_rows(G)
    f32 = jnp.float32
    scratch += [
        pltpu.VMEM((batch_size + 1, num_kv_heads, G, 1), f32),
        pltpu.VMEM((batch_size + 1, num_kv_heads, G, 1), f32),
        pltpu.VMEM((batch_size + 1, num_kv_heads, G, head_dim), f32),
        pltpu.VMEM((num_kv_heads, stacked, head_dim), f32),
        pltpu.VMEM((num_kv_heads, stacked, 1), f32),
        pltpu.VMEM((num_kv_heads, stacked, 1), f32),
        pltpu.VMEM((num_kv_heads, stacked, head_dim), f32),
    ]
    # the ring holds every KV head of three blocks; past the compiler's
    # default budget (16 MiB) with 16 and more KV heads, far inside the 128
    # MiB the core has
    ring_bytes = 2 * _NBUF * num_kv_heads * ppcb * page_size * head_dim * k_pages.dtype.itemsize
    # a [G, .] f32 tile pads to 8 sublanes, a column to 128 lanes: three such a slot and head
    state_bytes = 3 * (batch_size + 1) * num_kv_heads * -(-G // 8) * 8 * max(head_dim, 128) * 4

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            ppcb=ppcb,
            pps=pages_per_sequence,
            quant=quant,
            sm_scale=head_dim**-0.5 if sm_scale is None else float(sm_scale),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            in_specs=[vmem_spec] + [any_spec] * len(pages),
            out_specs=vmem_spec,
            grid=(1,),
            scratch_shapes=tuple(scratch),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * ring_bytes + state_bytes + (8 << 20)))
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, jnp.float32),
        name="paged_decode_attn",
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        page_indices.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *schedule,
        qg,
        *pages,
    )
    return out.reshape(batch_size, num_q_heads, head_dim).astype(q.dtype)
