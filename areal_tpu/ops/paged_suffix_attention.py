"""Paged suffix-attention kernel family: suffix-prefill + tree-verify.

The decode path (q_len=1) rides the stacked paged decode kernel
(ops/paged_attention_q8.py), but the two *batched-suffix* paths —
radix-warm suffix prefill (``qwen.forward_prefill_paged``) and
spec-decode tree verify (``qwen.forward_verify_paged``) — gathered every
prefix page into a dense [A, W, KH, hd] array and ran batched matmuls:
a full HBM read + write of the windowed prefix per layer on exactly the
paths every spec round and every radix-hit admission pays.

This module is a repo-native Pallas kernel computing a block of suffix
queries against page-table-indexed prefix KV plus the causal/tree-masked
in-flight suffix:

  - grid over (slot, kv_head, query tile, suffix key block). A query tile
    is ``bq`` suffix rows x the kv head's G group heads — at most
    ``_MAX_ROWS`` rows, so the tile, its f32 accumulator and its logits
    fit VMEM at every suffix bucket the engine produces (one untiled
    [B*G, hd] block did not from B=256 up)
  - the first key step of a tile walks the prefix: per-slot
    ``page_indices``/``prefix_lens`` arrive via scalar prefetch; prefix
    pages are DMA-ed HBM->VMEM in double-buffered blocks of
    ``pages_per_compute_block`` pages, so the gathered prefix never
    materializes in HBM
  - flash-style online softmax across prefix blocks and then across the
    suffix key blocks (running max / sum / accumulator in VMEM scratch) —
    the mask operand is the ONLY thing distinguishing the two launch
    variants: a causal chain mask gives suffix-prefill, an ancestor tree
    mask gives tree-verify (subsuming ops/tree_attention.py semantics on
    the paged pool)
  - int8 / float8_e4m3fn pages carry lane-major per-vector scales
    ([..., 1, psz], the ops/paged_attention_q8.py discipline: compact in
    HBM, DMA-sliceable, and applied to logit / probability COLUMNS so no
    in-VMEM relayout is needed); both dtypes share one formula because
    fp8 pages store ``x * 127.5 / scale`` (inference/paged_kv.py)

Row-validity convention: a suffix row attends the prefix iff its mask
DIAGONAL bit is set (mask[s, r, r]). ``qwen._attention_mask`` is
row-gated (padded rows attend nothing, diag included) and the drafter
sets every node's self bit (inference/speculative.py), so one rule serves
both variants. Rows with nothing valid anywhere output exact zeros —
``paged_suffix_attention_xla`` below is the bit-matching reference (the
model's dense ``_sdpa`` instead emits a garbage uniform average on such
rows; callers discard them either way, but the parity harness needs a
reference with identical semantics).

``interpret=None`` selects interpret mode off-TPU, so CPU tests exercise
the real kernel body. On a TPU the kernel is always compiled: a kernel
the chip's compiler refuses is an error, and callers choose kernel or
gather statically from the shapes (``paged_kernel_ok`` in
ops/paged_attention_q8.py), never from a caught compile error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# shared with inference/paged_kv.py quantize_kv: scale = max|x| over
# head_dim, stored value = x * 127.5 / scale (rint+clip for int8, raw cast
# for float8_e4m3fn) -> one dequant formula for both page dtypes
_MAX_INT8 = 127.5
_NEG_INF = -1e30
# most query rows (suffix rows x group heads) one grid cell keeps in VMEM,
# and most suffix keys one key step brings in
_MAX_ROWS = 512
_MAX_KEYS = 512


def _interp(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def default_ppcb(wp: int) -> int:
    """Pages a prefix block of the launch holds when the caller names none:
    the largest divisor of the window's ``wp`` pages up to 8."""
    return next(d for d in range(min(wp, 8), 0, -1) if wp % d == 0)


def _tiles(B: int, G: int) -> tuple[int, int, int]:
    """(Bp, bq, bk): the suffix length padded to the tiling (masked rows
    and columns, sliced off again), suffix rows per query tile and suffix
    keys per key step. ``bq`` is a sublane multiple dividing Bp with
    bq*G <= _MAX_ROWS; ``bk`` is Bp itself up to _MAX_KEYS, beyond that a
    lane-aligned divisor — so every B the engine produces (256-token
    buckets, a cap at max_seq_len, a handful of verify nodes) tiles."""
    if G * 8 > _MAX_ROWS:
        raise ValueError(f"group size {G} exceeds the kernel's {_MAX_ROWS} rows")
    step = 8 if B <= _MAX_KEYS else 128
    Bp = -(-B // step) * step
    bq = max(d for d in range(8, min(Bp, _MAX_ROWS // G) + 1, 8) if Bp % d == 0)
    if Bp <= _MAX_KEYS:
        return Bp, bq, Bp
    bk = max(d for d in range(128, _MAX_KEYS + 1, 128) if Bp % d == 0)
    return Bp, bq, bk


def _suffix_kernel(
    plens_ref,  # SMEM [S] int32 — prefix tokens per slot
    pidx_ref,  # SMEM [S * wp] int32 — flat page table
    layer_ref,  # SMEM [1] int32 — which layer's pages to read
    q_ref,  # [G*bq, hd] f32 — this tile's query rows, pre-scaled, g-major
    ks_ref,  # [bk, hd] — this key step's in-flight suffix K
    vs_ref,  # [bk, hd]
    mask_ref,  # [bq, bk] int32 — suffix validity (chain or tree)
    valid_ref,  # [bq, 128] int32 — row attends the prefix (lane-broadcast)
    *refs,
    wp: int,
    ppcb: int,
    num_groups: int,
    quant: bool,
):
    if quant:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref,
         k_vmem, ks_vmem, v_vmem, vs_vmem, sems, m_scr, l_scr, acc_scr) = refs
    else:
        k_hbm, v_hbm, o_ref, k_vmem, v_vmem, sems, m_scr, l_scr, acc_scr = refs
        ks_hbm = vs_hbm = ks_vmem = vs_vmem = None
    s, h, ik = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    li = layer_ref[0]
    plen = plens_ref[s]
    _, _, _, page_size, head_dim = k_hbm.shape
    rows = q_ref.shape[0]
    bs = ppcb * page_size  # tokens per prefix block
    nb = (plen + bs - 1) // bs  # prefix blocks this slot actually needs
    q = q_ref[...].astype(jnp.float32)  # [rows, hd]

    def tile_rows(x):
        # [bq, n] -> [G*bq, n]: row g*bq + i is suffix row i for every head g
        return jnp.concatenate([x] * num_groups, axis=0)

    def online_update(logits, valid, v, v_col_scale=None):
        """One flash step over a key block: fold [rows, n] logits (valid
        where ``valid``) and their values into the running scratch."""
        logits = jnp.where(valid, logits, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if v_col_scale is not None:
            p = p * v_col_scale
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    def block_copies(blk, slot):
        """Async-copy descriptors for prefix block ``blk`` -> buffer
        ``slot`` — built identically at start() and wait() time. A buffer's
        copies share that buffer's semaphore (it counts bytes), so block
        i+1's in-flight copies can never satisfy a wait on block i."""
        copies = []
        for j in range(ppcb):  # static unroll
            page = pidx_ref[s * wp + blk * ppcb + j]
            pairs = [(k_hbm, k_vmem), (v_hbm, v_vmem)]
            if quant:
                pairs += [(ks_hbm, ks_vmem), (vs_hbm, vs_vmem)]
            for hbm, vmem in pairs:
                copies.append(
                    pltpu.make_async_copy(
                        hbm.at[li, h, page], vmem.at[slot, j], sems.at[slot]
                    )
                )
        return copies

    def scale_row(buf, slot):
        # [ppcb, 1, psz] -> [1, bs]: the pages' lane-major scales side by side
        sc = buf[slot].astype(jnp.float32)
        return jnp.concatenate([sc[j] for j in range(ppcb)], axis=-1) / _MAX_INT8

    @pl.when(ik == 0)
    def _prefix():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # row attends the prefix iff its SELF bit is set
        row_valid = tile_rows(valid_ref[:, :1]) > 0  # [rows, 1]

        @pl.when(nb > 0)
        def _prologue():
            for c in block_copies(0, 0):
                c.start()

        def prefix_block(i, _):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < nb)
            def _next():  # overlap block i's compute with block i+1's DMA
                for c in block_copies(i + 1, 1 - slot):
                    c.start()

            for c in block_copies(i, slot):
                c.wait()
            k2 = k_vmem[slot].astype(jnp.float32).reshape(bs, head_dim)
            v2 = v_vmem[slot].astype(jnp.float32).reshape(bs, head_dim)
            logits = jax.lax.dot_general(
                q, k2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, bs]
            if quant:
                logits = logits * scale_row(ks_vmem, slot)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1) + i * bs
            online_update(
                logits,
                (col < plen) & row_valid,
                v2,
                scale_row(vs_vmem, slot) if quant else None,
            )
            return ()

        jax.lax.fori_loop(0, nb, prefix_block, ())

    # the in-flight suffix, one key block per grid step, gated entirely by
    # the mask operand
    logits = jax.lax.dot_general(
        q, ks_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, bk]
    online_update(
        logits, tile_rows(mask_ref[...]) > 0, vs_ref[...].astype(jnp.float32)
    )

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        # all-masked rows have l == 0 and acc == 0 -> exact zero output
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def paged_suffix_attention(
    q: jax.Array,  # [S, B, H, hd] — RAW (this wrapper applies 1/sqrt(hd))
    k_suffix: jax.Array,  # [S, B, KH, hd] — in-flight suffix KV (unquantized)
    v_suffix: jax.Array,
    k_pages: jax.Array,  # [L, KH, N, psz, hd] (bf16/f32, int8, or fp8)
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    prefix_lens: jax.Array,  # [S] int32 — tokens committed in pages
    page_indices: jax.Array,  # [S, wp] int32 — window's pages per slot
    suffix_mask: jax.Array,  # [S, B, B] bool — row attends col (chain/tree)
    *,
    k_scales: jax.Array | None = None,  # f32 [L, KH, N, 1, psz] (quant pages)
    v_scales: jax.Array | None = None,
    pages_per_compute_block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Suffix queries over paged prefix + masked in-flight suffix
    -> [S, B, H, hd]. One kernel body, two launch variants: a causal chain
    ``suffix_mask`` is suffix-prefill, an ancestor tree mask is
    spec-decode verify. Reads layer ``layer`` of the FULL stacked cache
    (sliced inside the kernel: a host-side layer slice would make XLA
    materialize every layer's pages per scan step). Scales, when given,
    are lane-major ([..., 1, psz])."""
    S, B, H, hd = q.shape
    L, KH, N, psz, hd_k = k_pages.shape
    wp = page_indices.shape[1]
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k/v page shapes differ: {k_pages.shape} {v_pages.shape}")
    if hd_k != hd:
        raise ValueError(f"head_dim mismatch {hd} vs {hd_k}")
    if H % KH:
        raise ValueError(f"H={H} not divisible by KH={KH}")
    if k_suffix.shape != (S, B, KH, hd):
        raise ValueError(f"k_suffix shape {k_suffix.shape} != {(S, B, KH, hd)}")
    if suffix_mask.shape != (S, B, B):
        raise ValueError(f"suffix_mask shape {suffix_mask.shape} != {(S, B, B)}")
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")
    if quant and k_scales.shape != (*k_pages.shape[:-2], 1, psz):
        raise ValueError(
            f"lane-major scales [..., 1, {psz}] expected, got {k_scales.shape}"
        )
    ppcb = pages_per_compute_block
    if ppcb is None:
        ppcb = default_ppcb(wp)
    if wp % ppcb:
        raise ValueError(f"wp={wp} not divisible by ppcb={ppcb}")

    G = H // KH
    B_in = B
    B, bq, bk = _tiles(B_in, G)
    if B != B_in:  # pad to the tiling: masked-out rows and columns
        pad = B - B_in
        q, k_suffix, v_suffix = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            for x in (q, k_suffix, v_suffix)
        )
        suffix_mask = jnp.pad(suffix_mask, ((0, 0), (0, pad), (0, pad)))
    nq, nk = B // bq, B // bk
    rows = G * bq
    # query rows of one tile are g-major (row g*bq + i): the [bq, bk] mask
    # tile then expands to the G heads by plain sublane concatenation
    qt = (
        (q.astype(jnp.float32) * hd**-0.5)
        .reshape(S, nq, bq, KH, G, hd)
        .transpose(0, 3, 1, 4, 2, 5)
        .reshape(S, KH, nq, rows, hd)
    )
    ks = jnp.transpose(k_suffix, (0, 2, 1, 3))  # [S, KH, B, hd]
    vs = jnp.transpose(v_suffix, (0, 2, 1, 3))
    mask = suffix_mask.astype(jnp.int32)
    row_valid = jnp.broadcast_to(
        suffix_mask[:, jnp.arange(B), jnp.arange(B)].astype(jnp.int32)[..., None],
        (S, B, 128),
    )  # the diagonal, lane-broadcast like flash_fwd_pallas's segment ids

    q_spec = pl.BlockSpec(
        (None, None, None, rows, hd), lambda s, h, iq, ik, *_: (s, h, iq, 0, 0)
    )
    kv_spec = pl.BlockSpec(
        (None, None, bk, hd), lambda s, h, iq, ik, *_: (s, h, ik, 0)
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def page_buf(dtype):
        return pltpu.VMEM((2, ppcb, psz, hd), dtype)

    def scale_buf(dtype):
        return pltpu.VMEM((2, ppcb, 1, psz), dtype)

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
        pltpu.SemaphoreType.DMA((2,)),  # one per landing buffer
        pltpu.VMEM((rows, 128), jnp.float32),  # running max (lane-broadcast)
        pltpu.VMEM((rows, 128), jnp.float32),  # running sum
        pltpu.VMEM((rows, hd), jnp.float32),  # accumulator
    ]

    out = pl.pallas_call(
        functools.partial(
            _suffix_kernel, wp=wp, ppcb=ppcb, num_groups=G, quant=quant
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                q_spec,
                kv_spec,
                kv_spec,
                pl.BlockSpec((None, bq, bk), lambda s, h, iq, ik, *_: (s, iq, ik)),
                pl.BlockSpec((None, bq, 128), lambda s, h, iq, ik, *_: (s, iq, 0)),
            ]
            + [any_spec] * len(pages),
            out_specs=q_spec,
            grid=(S, KH, nq, nk),
            scratch_shapes=tuple(scratch),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4
        ),
        out_shape=jax.ShapeDtypeStruct((S, KH, nq, rows, hd), jnp.float32),
        name="paged_suffix_attn",
        interpret=_interp(interpret),
    )(
        prefix_lens.astype(jnp.int32),
        page_indices.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qt,
        ks,
        vs,
        mask,
        row_valid,
        *pages,
    )
    return (
        out.reshape(S, KH, nq, G, bq, hd)
        .transpose(0, 2, 4, 1, 3, 5)
        .reshape(S, B, H, hd)[:, :B_in]
        .astype(q.dtype)
    )


def paged_suffix_attention_xla(
    q: jax.Array,  # [S, B, H, hd] — RAW
    k_suffix: jax.Array,  # [S, B, KH, hd]
    v_suffix: jax.Array,
    k_pages: jax.Array,  # [L, KH, N, psz, hd]
    v_pages: jax.Array,
    layer: jax.Array,
    prefix_lens: jax.Array,  # [S]
    page_indices: jax.Array,  # [S, wp]
    suffix_mask: jax.Array,  # [S, B, B] bool
    *,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
) -> jax.Array:
    """Pure-XLA reference with the kernel's EXACT semantics (gather +
    grouped einsum, f32, zero output on all-masked rows, prefix gated by
    the mask diagonal) — kernelcheck's ground truth and the fallback the
    model paths take when ``use_kernel=False``."""
    S, B, H, hd = q.shape
    KH, psz = k_pages.shape[1], k_pages.shape[3]
    G = H // KH
    wp = page_indices.shape[1]
    W = wp * psz

    def gather(pages, scales=False):
        lay = jax.lax.dynamic_index_in_dim(pages, layer, 0, keepdims=False)
        g = lay[:, page_indices]
        if scales:  # lane-major [.., 1, psz] -> [.., psz, 1]
            g = jnp.swapaxes(g, -1, -2)
        g = jnp.transpose(g, (1, 2, 3, 0, 4))
        return g.reshape(S, W, KH, g.shape[-1])

    kp = gather(k_pages).astype(jnp.float32)
    vp = gather(v_pages).astype(jnp.float32)
    if k_scales is not None:
        kp = kp * (gather(k_scales, scales=True) / _MAX_INT8)
        vp = vp * (gather(v_scales, scales=True) / _MAX_INT8)
    k_full = jnp.concatenate(
        [kp, k_suffix.astype(jnp.float32)], axis=1
    )  # [S, W+B, KH, hd]
    v_full = jnp.concatenate([vp, v_suffix.astype(jnp.float32)], axis=1)

    row_valid = suffix_mask[
        :, jnp.arange(B), jnp.arange(B)
    ]  # [S, B] — the diagonal
    pre_valid = (
        row_valid[:, :, None]
        & (jnp.arange(W)[None, :] < prefix_lens[:, None])[:, None, :]
    )  # [S, B, W]
    mask = jnp.concatenate([pre_valid, suffix_mask], axis=-1)  # [S, B, W+B]

    qg = q.astype(jnp.float32).reshape(S, B, KH, G, hd)
    logits = (
        jnp.einsum("sbkgd,stkd->skgbt", qg, k_full) * hd**-0.5
    )  # [S, KH, G, B, W+B]
    m = jnp.where(mask[:, None, None], logits, _NEG_INF)
    mx = jnp.max(m, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, None], jnp.exp(m - mx), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("skgbt,stkd->sbkgd", p / denom, v_full)
    return o.reshape(S, B, H, hd).astype(q.dtype)
