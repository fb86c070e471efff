"""Paged decode attention over the FULL stacked KV cache: one repo-native
Pallas kernel for bf16 and int8/fp8 pages.

``paged_attention_stacked`` takes the stacked cache [n_layers, KH, N, psz,
hd] plus a (traced) layer index delivered via scalar prefetch, and slices
``ref.at[li]`` INSIDE the kernel. A pallas operand must be a real buffer,
so feeding the kernel a ``dynamic_index_in_dim`` layer slice from the
layers scan makes XLA materialize a copy of every layer's pages every
step: full-cache read+write traffic per decode step. In-kernel slicing
DMAs only the pages attention actually reads.

The algorithm is the one jax's library kernel uses
(jax.experimental.pallas.ops.tpu.paged_attention, Apache-2.0): a grid over
(slot, kv_head), the sequence walked inline in blocks of
``pages_per_compute_block`` pages, double-buffered HBM->VMEM page DMA in
which every block prefetches the NEXT block — across cell boundaries, so
a cell never starts on a cold buffer — and flash-style online softmax. The
body is written here rather than imported because the library's private
body cannot serve quantized pages on the chip:

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


def paged_kernel_ok(head_dim: int, page_size: int, quant: bool) -> bool:
    """Static shape rule of the COMPILED paged kernels (this module's decode
    kernel and ops/paged_suffix_attention.py; interpret mode takes any
    shape): a page's [page_size, head_dim] slice and, under quantization,
    its [1, page_size] scale row must be DMA-sliceable — lane-aligned
    (what the chip's compiler answered for a described v5e: head_dim 64
    and quantized 32/64-token pages are refused, bf16 pages of 8 tokens
    and up are taken). Engines outside it take the gather path."""
    return head_dim % 128 == 0 and page_size % (128 if quant else 8) == 0


def _decode_kernel(
    lengths_ref,  # SMEM [S] int32 — valid tokens per slot
    pidx_ref,  # SMEM [S * pps] int32 — flat page table
    layer_ref,  # SMEM [1] int32 — which layer's pages to read
    q_ref,  # [G, hd] — this cell's query rows (pre-scaled)
    *refs,
    batch_size: int,
    ppcb: int,
    pps: int,
    quant: bool,
):
    if quant:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref,
         k_buf, ks_buf, v_buf, vs_buf, k_sems, v_sems, state) = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sems, v_sems, state = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b, h = pl.program_id(0), pl.program_id(1)
    li = layer_ref[0]
    _, num_kv_heads, _, psz, hd = k_hbm.shape
    bk = ppcb * psz  # tokens per compute block
    length = lengths_ref[b]

    # state[0]: VMEM buffer holding the CURRENT block; state[1]: 1 until the
    # first block of the whole grid has issued its own copy. SMEM scratch
    # persists across grid cells (both axes are sequential).
    @pl.when((b == 0) & (h == 0))
    def _reset():
        state[0] = 0
        state[1] = 1

    def copies(b_, h_, i_, slot):
        """(K copies, V copies) of block ``i_`` of cell (b_, h_) into buffer
        ``slot`` — built identically at start() and wait() time; a slot's
        copies share one semaphore (it counts bytes)."""
        kc, vc = [], []
        for j in range(ppcb):  # static unroll
            page = pidx_ref[b_ * pps + i_ * ppcb + j]
            kc.append(pltpu.make_async_copy(
                k_hbm.at[li, h_, page], k_buf.at[slot, j], k_sems.at[slot]))
            vc.append(pltpu.make_async_copy(
                v_hbm.at[li, h_, page], v_buf.at[slot, j], v_sems.at[slot]))
            if quant:
                kc.append(pltpu.make_async_copy(
                    ks_hbm.at[li, h_, page], ks_buf.at[slot, j], k_sems.at[slot]))
                vc.append(pltpu.make_async_copy(
                    vs_hbm.at[li, h_, page], vs_buf.at[slot, j], v_sems.at[slot]))
        return kc, vc

    def next_block(i):
        """Grid-order successor of block ``i`` of this cell: the cell's next
        block, else block 0 of the next kv head, else of the next slot with
        a nonzero length (``batch_size`` when there is none)."""

        def next_slot():
            nb = jax.lax.fori_loop(
                b + 1,
                batch_size,
                lambda s, cur: jnp.where(
                    (cur == s) & (lengths_ref[s] == 0), s + 1, cur
                ),
                b + 1,
            )
            return nb, jnp.int32(0), jnp.int32(0)

        def next_head():
            return jax.lax.cond(
                h + 1 < num_kv_heads,
                lambda: (b, h + 1, jnp.int32(0)),
                next_slot,
            )

        return jax.lax.cond(
            (i + 1) * bk < length, lambda: (b, h, i + 1), next_head
        )

    def scale_row(buf, slot):
        # [ppcb, 1, psz] -> [1, bk]: the pages' lane-major scales side by side
        s = buf[slot].astype(jnp.float32)
        return jnp.concatenate([s[j] for j in range(ppcb)], axis=-1) / _MAX_INT8

    q = q_ref[...].astype(jnp.float32)  # [G, hd]
    G = q.shape[0]

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = state[0]

        @pl.when(state[1] == 1)
        def _first():  # nobody prefetched the grid's very first block
            kc, vc = copies(b, h, i, slot)
            for c in kc + vc:
                c.start()

        state[1] = 0
        nb, nh, ni = next_block(i)

        @pl.when(nb < batch_size)
        def _prefetch():  # overlaps this block's compute, across cells too
            kc, vc = copies(nb, nh, ni, 1 - slot)
            for c in kc + vc:
                c.start()

        state[0] = jnp.where(nb < batch_size, 1 - slot, slot)

        kc, vc = copies(b, h, i, slot)
        for c in kc:
            c.wait()
        k = k_buf[slot].astype(jnp.float32).reshape(bk, hd)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G, bk]
        if quant:
            logits = logits * scale_row(ks_buf, slot)
        col = i * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        logits = jnp.where(col < length, logits, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        for c in vc:
            c.wait()
        v = v_buf[slot].astype(jnp.float32).reshape(bk, hd)
        if quant:
            p = p * scale_row(vs_buf, slot)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    init = (
        jnp.full((G, 1), _MASK_VALUE, jnp.float32),
        jnp.zeros((G, 1), jnp.float32),
        jnp.zeros((G, hd), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, (length + bk - 1) // bk, block, init)
    # a zero-length slot never enters the loop: l == 0, acc == 0 -> zeros
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    q: jax.Array,  # [S, H, hd] — RAW (this wrapper applies 1/sqrt(hd))
    k_pages: jax.Array,  # [n_layers, KH, N, psz, hd] (bf16, int8 or fp8)
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    lengths: jax.Array,  # i32 [S]
    page_indices: jax.Array,  # i32 [S, pages_per_sequence]
    *,
    pages_per_compute_block: int,
    k_scales: jax.Array | None = None,  # f32 [n_layers, KH, N, 1, psz]
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged attention reading layer ``layer`` of the FULL stacked cache —
    zero layer-slice copies (see module docstring). Scales, when given,
    are lane-major ([..., 1, psz]) end to end."""
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

    G = num_q_heads // num_kv_heads
    # [S, H, hd] -> [S, KH, G, hd] is a free reshape, and a (G, hd) block is
    # the array's full trailing dims — legal for any group size
    qg = (q.astype(jnp.float32) * head_dim**-0.5).reshape(
        batch_size, num_kv_heads, G, head_dim
    )
    q_spec = pl.BlockSpec((None, None, G, head_dim), lambda b, h, *_: (b, h, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def page_buf(dtype):
        return pltpu.VMEM((2, ppcb, page_size, head_dim), dtype)

    def scale_buf(dtype):
        return pltpu.VMEM((2, ppcb, 1, page_size), dtype)

    if quant:
        pages = [k_pages, k_scales, v_pages, v_scales]
        scratch = [
            page_buf(k_pages.dtype), scale_buf(k_scales.dtype),
            page_buf(v_pages.dtype), scale_buf(v_scales.dtype),
        ]
    else:
        pages = [k_pages, v_pages]
        scratch = [page_buf(k_pages.dtype), page_buf(v_pages.dtype)]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),  # K copies, one per buffer
        pltpu.SemaphoreType.DMA((2,)),  # V copies
        pltpu.SMEM((2,), jnp.int32),  # (current buffer, first-block flag)
    ]

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            batch_size=batch_size,
            ppcb=ppcb,
            pps=pages_per_sequence,
            quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[q_spec] + [any_spec] * len(pages),
            out_specs=q_spec,
            grid=(batch_size, num_kv_heads),
            scratch_shapes=tuple(scratch),
        ),
        compiler_params=pltpu.CompilerParams(
            # sequential on purpose: a block prefetches its grid-order
            # successor, so cells must run in order on one core
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, jnp.float32),
        name="paged_decode_attn",
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        page_indices.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qg,
        *pages,
    )
    return out.reshape(batch_size, num_q_heads, head_dim).astype(q.dtype)
