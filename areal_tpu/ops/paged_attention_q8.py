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
(``decode_schedule``: one item per live slot and block of
``pages_per_compute_block`` pages that holds tokens of it, slot-major) is the
same for every layer, so a decode step computes it once and hands it to each
layer's launch by scalar prefetch; a slot of length 0 is in no item, costs
nothing and returns exact zeros. An item covers EVERY KV head of its block:
ONE copy a page and pool moves that page of all the heads (a strided window
``[KH, psz, hd]`` of the pool) into one of three VMEM buffers. The loop body
is instruction issue before it is anything else (PERF.md, Findings, PR 41:
the scalar core builds a copy's descriptor and its bounds checks in about 21
VLIW bundles, and with a copy a head they were half of the body's 711 at 2 KV
heads and more at 4 and 30; the launch took bundles x items at 0.9 GHz, not
its bytes' time). While item t is computed the copies of items t+1 and t+2
are in flight — across slot boundaries, so no item but the first starts on a
cold buffer — and item t+3's start at the end of item t's trip, into the
buffer it has just left (measured on the v5e: the third buffer is worth
5-10%, a fourth nothing; started at the end of the trip one item less ahead,
the launch is 15% slower). A slot's last block fetches only the pages that
hold tokens.

A trip is ONE basic block but for the store at a slot's last block (every
branch less is instructions less, and the compiler schedules within a block):
the waits first; then QK of every head, and only then, head by head, the
softmax and PV (alternating K's transposed and V's plain pushes into the MXU
head by head costs 3% on bf16 pages and 20-40% on int8 at the same bundle
count); then the prefetch, whose page conditions also say "the item exists".
Each item computes its block's OWN flash-style statistics in f32 (the
block's maximum, sum and accumulator, no read of the slot's running state)
and merges them into the running state carried through the loop by the usual
two-term rescale, reset at a slot's first block. Two items a trip, and QK of
item t+1 under item t's softmax, were built and measured and are not here:
no fewer bundles an item, and slower on the chip.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_INT8 = 127.5
_MASK_VALUE = -1e30
# blocks of pages in VMEM at once: one computed on, two in flight (measured
# on the v5e: a third buffer is worth 5-10%, a fourth nothing)
_NBUF = 3


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
    """The launch's work list, the same for every layer of a decode step (so
    a step computes it once and hands it to each launch): one item per
    (live slot, block of its tokens), slot-major. Returns (slot of item t,
    block of item t, [number of items]); entries past the count are never
    read."""
    ppcb = pages_per_compute_block
    bk = ppcb * page_size
    num_slots = lengths.shape[0]
    nblk = (lengths.astype(jnp.int32) + bk - 1) // bk
    end = jnp.cumsum(nblk)
    t = jnp.arange(num_slots * (pages_per_sequence // ppcb), dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(t[:, None] >= end[None, :], axis=1), num_slots - 1)
    block = t - (end - nblk)[slot]
    return slot, block, end[-1:]


def live_order(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(slot indices with the live ones first, how many are live) of a [S]
    bool mask: the work list of the kernels that visit a slot once
    (ops/paged_kv_write.py, ops/ssm_state_update.py), the same for every
    layer of a step."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live).astype(jnp.int32)


def _decode_kernel(
    lengths_ref,  # SMEM [S] int32 — valid tokens per slot
    pidx_ref,  # SMEM [S * pps] int32 — flat page table
    layer_ref,  # SMEM [1] int32 — which layer's pages to read
    item_slot_ref,  # SMEM [S * pps / ppcb] int32 — decode_schedule()
    item_block_ref,
    num_items_ref,  # SMEM [1] int32
    q_ref,  # VMEM [S, KH, G, hd] — raw queries (``sm_scale`` is applied here)
    *refs,
    ppcb: int,
    pps: int,
    quant: bool,
    sm_scale: float,
):
    if quant:
        k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, k_buf, ks_buf, v_buf, vs_buf, sems = refs
        pools = ((k_hbm, k_buf), (ks_hbm, ks_buf), (v_hbm, v_buf), (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
        ks_buf = vs_buf = None
    li = layer_ref[0]
    num_items = num_items_ref[0]
    max_items = item_slot_ref.shape[0]
    _, num_kv_heads, _, psz, hd = k_hbm.shape
    nbuf = k_buf.shape[0]
    G = q_ref.shape[2]
    bk = ppcb * psz  # tokens per compute block

    # a slot no item names (length 0) keeps these zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
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
        live = t < num_items
        t = jnp.minimum(t, max_items - 1)
        b, buf = item_slot_ref[t], t % nbuf
        i = jnp.where(live, item_block_ref[t], 0)  # no table entry past the slot's row is read
        held = jnp.where(live, (lengths_ref[b] - i * bk + psz - 1) // psz, 0)  # pages with tokens

        def page(j):
            pg = pidx_ref[b * pps + i * ppcb + j]
            for hbm, vmem in pools:
                go(pltpu.make_async_copy(hbm.at[li, :, pg], vmem.at[buf, :, j], sems.at[buf]))

        for j in range(ppcb):
            pl.when(j < held)(functools.partial(page, j))

    def scale_row(buf_ref, buf, h):
        # [ppcb, 1, psz] -> [1, bk]: the pages' lane-major scales side by side
        s = buf_ref[buf, h].astype(jnp.float32)
        return jnp.concatenate([s[j] for j in range(ppcb)], axis=-1) / _MAX_INT8

    for t in range(nbuf):  # fill the ring
        copies(t, start)

    def item(t, carry):
        b, i, buf = item_slot_ref[t], item_block_ref[t], t % nbuf
        length = lengths_ref[b]
        first = i == 0
        col = i * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        valid = col < length
        copies(t, wait)
        logits = []
        for h in range(num_kv_heads):
            q = q_ref[b, h].astype(jnp.float32)  # [G, hd]
            k = k_buf[buf, h].astype(jnp.float32).reshape(bk, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * sm_scale  # [G, bk]
            if quant:
                s = s * scale_row(ks_buf, buf, h)
            logits.append(jnp.where(valid, s, _MASK_VALUE))
        out = []
        for h in range(num_kv_heads):
            # the block's own statistics, against its own maximum: nothing
            # here reads the slot's running state
            m_blk = jnp.max(logits[h], axis=-1, keepdims=True)  # an item holds a valid token
            p = jnp.exp(logits[h] - m_blk)
            l_blk = jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * scale_row(vs_buf, buf, h)
            v = v_buf[buf, h].astype(jnp.float32).reshape(bk, hd)
            acc_blk = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            # merged into the running state after: the two-term rescale
            m_prev, l_prev, acc_prev = carry[h]
            m_prev = jnp.where(first, _MASK_VALUE, m_prev)  # exp(_MASK_VALUE - m) is exactly 0
            m_new = jnp.maximum(m_prev, m_blk)
            a = jnp.exp(m_prev - m_new)
            c = jnp.exp(m_blk - m_new)
            out.append((m_new, l_prev * a + l_blk * c, acc_prev * a + acc_blk * c))
        # into the buffer this item has just left: two items stay in flight
        copies(t + nbuf, start)

        @pl.when(i == (length + bk - 1) // bk - 1)
        def _store():  # the slot's last block
            for h, (_, l_new, acc) in enumerate(out):
                o_ref[b, h] = (acc / l_new).astype(o_ref.dtype)

        return tuple(out)

    init = tuple(
        (
            jnp.full((G, 1), _MASK_VALUE, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, hd), jnp.float32),
        )
        for _ in range(num_kv_heads)
    )
    jax.lax.fori_loop(0, num_items, item, init)


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
    schedule: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    k_scales: jax.Array | None = None,  # f32 [n_layers, KH, N, 1, psz]
    v_scales: jax.Array | None = None,
    sm_scale: float | None = None,  # softmax scale; default 1/sqrt(hd)
    interpret: bool = False,
) -> jax.Array:
    """Paged attention reading layer ``layer`` of the FULL stacked cache —
    zero layer-slice copies (see module docstring). Scales, when given,
    are lane-major ([..., 1, psz]) end to end. ``schedule`` is
    ``decode_schedule()`` of the same lengths, table width and block size,
    for a caller that launches once per layer; computed here otherwise."""
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
        schedule = decode_schedule(lengths, pages_per_sequence, page_size, ppcb)
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
    # the ring holds every KV head of three blocks; past the compiler's
    # default budget (16 MiB) with 16 and more KV heads, far inside the 128
    # MiB the core has
    ring_bytes = 2 * _NBUF * num_kv_heads * ppcb * page_size * head_dim * k_pages.dtype.itemsize

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            ppcb=ppcb,
            pps=pages_per_sequence,
            quant=quant,
            sm_scale=head_dim**-0.5 if sm_scale is None else float(sm_scale),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[vmem_spec] + [any_spec] * len(pages),
            out_specs=vmem_spec,
            grid=(1,),
            scratch_shapes=tuple(scratch),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * ring_bytes + (8 << 20)))
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
