"""Pallas TPU flash attention for packed training rows: the forward, dK/dV
and dQ kernels behind ``ops.attention.flash_train``, with their custom VJP.

Forked from jax 0.9.0's ``jax/experimental/pallas/ops/tpu/flash_attention.py``
(Copyright 2023 The JAX Authors, Apache License 2.0) and trimmed to what the
trainer calls: causal, segment ids always, no attention bias, one batch row
a block, one tile edge per axis (the library's major and minor blocks
equal), ``head_dim`` a multiple of 128. The arithmetic inside a tile is the
library's, line for line.

What the fork adds is the reason for it: **a tile that holds no
same-segment pair is skipped**. The library skips a (query tile, key tile)
pair by the causal diagonal alone; segment ids are a mask inside every tile
it runs, and a packed row is mostly pairs of different sequences (PERF.md,
PR 33). The wrappers take each tile's smallest and largest segment id
(``tile_ranges``), decide every tile once (``live_tiles``) and hand the
kernels the verdicts as scalar prefetch (``flash_skips``); a tile runs
when

    below_or_on_diag & (q_lo <= k_hi) & (k_lo <= q_hi)

A range test on true minima and maxima never skips a tile that holds an
equal pair, whatever the ids (unsorted, a zero-padded tail; id 0 is an id
like another to the mask, as in the library), and the mask inside a tile
that runs stays. A skipped tile adds exact zeros, so leaving it out is the same
mathematics. Its streamed blocks (K/V forward and dQ; Q, dO, l, m, di in
dK/dV) are redirected to the block the next running tile reads, so a
skipped step issues no DMA of its own and the next tile's fetch starts
under the last tile's compute. The library did the same for its causal
skip with block 0.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.dtype("float32")).max)
NUM_LANES = 128
NUM_SUBLANES = 8
TRANS_B = (((1,), (1,)), ((), ()))
_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


class FlashBlocks(NamedTuple):
    """(query edge, key edge) of each kernel's tile."""

    fwd: tuple[int, int]
    dkv: tuple[int, int]
    dq: tuple[int, int]


class FlashSkips(NamedTuple):
    """Each kernel's scalar prefetch (``skip_operands``): a (run, block)
    pair of int32 tables."""

    fwd: tuple[jax.Array, jax.Array]
    dkv: tuple[jax.Array, jax.Array]
    dq: tuple[jax.Array, jax.Array]


def below_or_on_diag(r, r_blk: int, c, c_blk: int):
    """The tile's bottom left corner is on or below the diagonal."""
    return ((r + 1) * r_blk - 1) > (c * c_blk)


def tile_ranges(segment_ids, edge: int):
    """Smallest and largest segment id of each tile of ``edge`` tokens:
    two ``[G, L // edge]`` arrays (numpy in, numpy out; jax in, jax out).
    Id 0 is ranged as the largest integer: a packed row numbers its
    sequences 1, 2, ... and pads its tail with 0 (``utils/grid.py
    pack_grid``), so the ids then rise along the row and the tile that
    holds the last sequence's end does not span every id before it. Any
    relabelling keeps equal ids equal, so the test still never skips a
    tile that holds an equal pair; the mask inside a tile sees the ids as
    they are."""
    G, L = segment_ids.shape
    ids = (jnp if isinstance(segment_ids, jax.Array) else np).where(segment_ids == 0, np.iinfo(np.int32).max, segment_ids)
    tiles = ids.reshape(G, L // edge, edge)
    return tiles.min(axis=-1), tiles.max(axis=-1)


def causal_tiles(L: int, block_q: int, block_k: int):
    """``[L // block_q, L // block_k]`` bool: the tiles on or below the
    diagonal, all the library's kernels skipped by."""
    return below_or_on_diag(np.arange(L // block_q)[:, None], block_q, np.arange(L // block_k)[None, :], block_k)


def live_tiles(segment_ids, block_q: int, block_k: int):
    """``[G, L // block_q, L // block_k]`` bool: the tiles the kernels run."""
    q_lo, q_hi = tile_ranges(segment_ids, block_q)
    k_lo, k_hi = (q_lo, q_hi) if block_k == block_q else tile_ranges(segment_ids, block_k)
    overlap = (q_lo[:, :, None] <= k_hi[:, None, :]) & (k_lo[:, None, :] <= q_hi[:, :, None])
    return causal_tiles(segment_ids.shape[1], block_q, block_k)[None] & overlap


def flash_skips(segment_ids, blocks: FlashBlocks) -> FlashSkips:
    """The three kernels' scalar prefetch for one grid of segment ids. A
    few dozen tiny XLA ops of some microseconds each that XLA leaves inside
    a scan over layers: a model computes them once a forward pass and hands
    them to every layer (``ops.attention.flash_mask``)."""
    return FlashSkips(
        fwd=skip_operands(segment_ids, *blocks.fwd, outer="q"),
        dkv=skip_operands(segment_ids, *blocks.dkv, outer="k"),
        dq=skip_operands(segment_ids, *blocks.dq, outer="q"),
    )


def skip_operands(segment_ids, block_q: int, block_k: int, outer: str):
    """One kernel's scalar prefetch, one entry a grid step: two
    ``[G, n_outer * n_inner]`` int32 tables over the OUTER grid axis ('q'
    or 'k' tiles) and the inner one. ``run``: 1 where the tile runs.
    ``block``: the inner block index a streamed operand holds at that
    step: the tile's own where it runs, else the next running tile's
    (this outer row's first while it is still ahead, then the next row's;
    row 0 of the next head or batch row starts at tile 0, which always
    runs). The verdicts are computed here, once a call, and not from the
    ranges at every step: a grid step costs 0.35 us and the scalar core
    adds 0.13 us to it for four range reads and the redirect in three
    index maps (PERF.md, PR 33)."""
    live = live_tiles(segment_ids, block_q, block_k)
    if outer == "k":
        live = live.transpose(0, 2, 1)
    G, n_outer, n_inner = live.shape
    inner = np.arange(n_inner, dtype=np.int32)
    first = jnp.argmax(live, axis=2).astype(jnp.int32)[..., None]
    following = jnp.concatenate([first[:, 1:], jnp.zeros_like(first[:, :1])], axis=1)
    block = jnp.where(live, inner, jnp.where(inner < first, first, following))
    return live.astype(jnp.int32).reshape(G, -1), block.reshape(G, -1)


def _tile_mask(qseg, kseg, row0, col0):
    """Same segment AND causal inside one tile. ``qseg`` [block_q, 128]
    (ids broadcast along lanes), ``kseg`` [1, block_k]."""
    block_q, block_k = qseg.shape[0], kseg.shape[1]
    same = jnp.tile(qseg, (1, block_k // NUM_LANES)) == kseg
    rows = row0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return same & (cols <= rows)


def _lanes(x, width: int):
    """[n, 128] -> [n, width]: a lane-broadcast column repeated."""
    return jnp.tile(x, (1, width // NUM_LANES))


def _segment_operands(segment_ids):
    """Segment ids broadcast into lanes (query side) and sublanes (key
    side), the layouts the kernels' tilings want."""
    G, L = segment_ids.shape
    return (
        lax.broadcast_in_dim(segment_ids, (G, L, NUM_LANES), (0, 1)),
        lax.broadcast_in_dim(segment_ids, (G, NUM_SUBLANES, L), (0, 2)),
    )


def _check_blocks(name: str, L: int, block_q: int, block_k: int) -> None:
    for edge in (block_q, block_k):
        if edge % NUM_LANES or L % edge:
            raise ValueError(f"{name} tile edge {edge} must be a multiple of {NUM_LANES} that divides L={L}")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    run_ref, block_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    o_ref, l_ref, m_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale: float, block_q: int, block_k: int,
):
    del block_ref
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    head_dim = q_ref.shape[-1]

    @pl.when(ki == 0)
    def start_new_sequence():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(run_ref[b, qi * pl.num_programs(3) + ki] != 0)
    def run():
        m_prev, l_prev = m_scr[...], l_scr[...]
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = lax.dot_general(q, k, TRANS_B, preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s *= sm_scale
        mask = _tile_mask(qseg_ref[0], kseg_ref[0, :1], qi * block_q, ki * block_k)
        s = s + jnp.where(mask, 0.0, MASK_VALUE)

        m_curr = jnp.max(s, axis=1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)  # [block_q, 128]
        p = jnp.exp(s - _lanes(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_corr = alpha * l_prev
        l_next = jnp.sum(p, axis=1)[:, None] + l_corr
        l_scr[...], m_scr[...] = l_next, m_next

        l_next_inv_safe = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
        acc_scr[...] *= _lanes(l_corr * l_next_inv_safe, head_dim)
        o_curr = lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_scr[...] += o_curr * _lanes(l_next_inv_safe, head_dim)

    @pl.when(ki == pl.num_programs(3) - 1)
    def store_output():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        if l_ref is not None:
            l_ref[0, 0] = l_scr[...]
            m_ref[0, 0] = m_scr[...]


def _fwd_kernel_one_key_tile(
    run_ref, block_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, l_ref, m_ref, *, sm_scale: float, block_q: int
):
    """The whole row's keys in one tile: no running softmax, and nothing to
    skip (every query tile meets its own tokens there)."""
    del run_ref, block_ref
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    s = lax.dot_general(q, k, TRANS_B, preferred_element_type=jnp.float32)
    if sm_scale != 1.0:
        s *= sm_scale
    mask = _tile_mask(qseg_ref[0], kseg_ref[0, :1], pl.program_id(2) * block_q, 0)
    s = s + jnp.where(mask, 0.0, MASK_VALUE)
    m = jnp.max(s, axis=1)[:, None]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1)[:, None]
    p /= l
    if l_ref is not None:
        m_ref[0, 0] = lax.broadcast_in_dim(m, m_ref.shape[2:], range(2))
        l_ref[0, 0] = lax.broadcast_in_dim(l, l_ref.shape[2:], range(2))
    o_ref[0, 0] = lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _flash_fwd(q, k, v, segment_ids, prefetch, *, save_residuals: bool, sm_scale: float, block_q: int, block_k: int, interpret: bool):
    G, H, L, d = q.shape
    _check_blocks("forward", L, block_q, block_k)
    n_q, n_k = L // block_q, L // block_k

    def q_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki, run_ref, block_ref):
        return (b, h, block_ref[b, qi * n_k + ki], 0)

    def qseg_map(b, h, qi, ki, *_):
        return (b, qi, 0)

    def kseg_map(b, h, qi, ki, run_ref, block_ref):
        return (b, 0, block_ref[b, qi * n_k + ki])

    lm_shape = jax.ShapeDtypeStruct((G, H, L, NUM_LANES), jnp.float32)
    lm_spec = pl.BlockSpec((1, 1, block_q, NUM_LANES), q_map)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype), *([lm_shape] * 2 if save_residuals else [None] * 2)]
    out_specs = [pl.BlockSpec((1, 1, block_q, d), q_map), *([lm_spec] * 2 if save_residuals else [None] * 2)]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
        pl.BlockSpec((1, block_q, NUM_LANES), qseg_map),
        pl.BlockSpec((1, NUM_SUBLANES, block_k), kseg_map),
    ]
    qseg, kseg = _segment_operands(segment_ids)
    if n_k == 1:
        kernel = functools.partial(_fwd_kernel_one_key_tile, sm_scale=sm_scale, block_q=block_q)
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k)
        scratch = [
            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ]
    operands = (q, k, v, qseg, kseg)
    o, *lm = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # skip_operands
            grid=(G, H, n_q, n_k),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        # the full [L, L] square, as the library's estimate from its
        # reference counted: what XLA schedules the neighbours by
        cost_estimate=pl.CostEstimate(
            flops=4 * G * H * L * L * d,
            transcendentals=G * H * L * L,
            bytes_accessed=sum(x.size * x.dtype.itemsize for x in (*operands, *(s for s in out_shape if s is not None))),
        ),
        name=f"flash_mha_fwd_block_q_{block_q}_block_k_major_{block_k}_block_k_{block_k}",
        interpret=interpret,
    )(*prefetch, *operands)
    if save_residuals:
        return o, lm[0][..., 0], lm[1][..., 0]
    return o


# ---------------------------------------------------------------------------
# backward: dK and dV (grid over key tiles, query tiles innermost)
# ---------------------------------------------------------------------------


def _probs_and_ds(q, k, v, l, m, do, di, mask, sm_scale: float):
    """One tile's probabilities and d(logits), as both backward kernels
    recompute them. l, m, di are [block_q, 128] lane-broadcast columns."""
    block_k = k.shape[0]
    logits = lax.dot_general(q, k, TRANS_B, preferred_element_type=jnp.float32)
    if sm_scale != 1.0:
        logits *= sm_scale
    logits = logits + jnp.where(mask, 0.0, MASK_VALUE)
    p = jnp.exp(logits - _lanes(m, block_k))
    p = p * _lanes(1 / l, block_k)
    dp = lax.dot_general(do, v, TRANS_B, preferred_element_type=jnp.float32)
    ds = (dp - _lanes(di, block_k)) * p
    if sm_scale != 1.0:
        ds = ds * sm_scale
    return p, ds


def _dkv_kernel(
    run_ref, block_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, l_ref, m_ref, do_ref, di_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale: float, block_q: int, block_k: int,
):
    del block_ref
    b, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def start_new_sequence():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    @pl.when(run_ref[b, ki * pl.num_programs(3) + qi] != 0)
    def run():
        q, do = q_ref[0, 0], do_ref[0, 0]
        mask = _tile_mask(qseg_ref[0], kseg_ref[0, :1], qi * block_q, ki * block_k)
        p, ds = _probs_and_ds(
            q, k_ref[0, 0], v_ref[0, 0], l_ref[0, 0], m_ref[0, 0], do, di_ref[0, 0].astype(jnp.float32), mask, sm_scale
        )
        dv_scr[...] += lax.dot(p.T.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dk_scr[...] += lax.dot(ds.T.astype(do.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def end_of_q_sequence():
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)


def _flash_bwd_dkv(q, k, v, segment_ids, prefetch, l, m, do, di, *, sm_scale: float, block_q: int, block_k: int, interpret: bool):
    G, H, L, d = q.shape
    _check_blocks("dkv", L, block_q, block_k)
    # broadcast out scalar values
    m = jnp.broadcast_to(m[..., None], (*m.shape, NUM_LANES))
    l = jnp.broadcast_to(l[..., None], (*l.shape, NUM_LANES))
    di = jnp.broadcast_to(di[..., None], (*di.shape, NUM_LANES))

    # the key tile comes before the query tile: queries are contracted
    n_q = L // block_q

    def q_map(b, h, ki, qi, run_ref, block_ref):
        return (b, h, block_ref[b, ki * n_q + qi], 0)

    def kv_map(b, h, ki, qi, *_):
        return (b, h, ki, 0)

    def qseg_map(b, h, ki, qi, run_ref, block_ref):
        return (b, block_ref[b, ki * n_q + qi], 0)

    def kseg_map(b, h, ki, qi, *_):
        return (b, 0, ki)

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), kv_map)
    col_spec = pl.BlockSpec((1, 1, block_q, NUM_LANES), q_map)
    qseg, kseg = _segment_operands(segment_ids)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # skip_operands
            grid=(G, H, L // block_k, L // block_q),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, block_q, NUM_LANES), qseg_map),
                pl.BlockSpec((1, NUM_SUBLANES, block_k), kseg_map),
                col_spec, col_spec, q_spec, col_spec,
            ],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32), pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        # the library's name for these tiles (its major and minor blocks
        # equal): what the ledger's breakdown lists the kernel under
        name=f"flash_mha_bwd_dkv_block_q_major_{block_q}_block_q_{block_q}_block_k_major_{block_k}_block_k_{block_k}",
        interpret=interpret,
    )(*prefetch, q, k, v, qseg, kseg, l, m, do, di)


# ---------------------------------------------------------------------------
# backward: dQ (grid over query tiles, key tiles innermost)
# ---------------------------------------------------------------------------


def _dq_kernel(
    run_ref, block_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, l_ref, m_ref, do_ref, di_ref,
    dq_ref,
    dq_scr,
    *, sm_scale: float, block_q: int, block_k: int,
):
    del block_ref
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def start_new_sequence():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    @pl.when(run_ref[b, qi * pl.num_programs(3) + ki] != 0)
    def run():
        k = k_ref[0, 0]
        mask = _tile_mask(qseg_ref[0], kseg_ref[0, :1], qi * block_q, ki * block_k)
        _, ds = _probs_and_ds(
            q_ref[0, 0], k, v_ref[0, 0], l_ref[0, 0], m_ref[0, 0], do_ref[0, 0], di_ref[0, 0].astype(jnp.float32), mask, sm_scale
        )
        dq_scr[...] += lax.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def end_of_kv_sequence():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dq(q, k, v, segment_ids, prefetch, l, m, do, di, *, sm_scale: float, block_q: int, block_k: int, interpret: bool):
    G, H, L, d = q.shape
    _check_blocks("dq", L, block_q, block_k)
    m = jnp.broadcast_to(m[..., None], (*m.shape, NUM_LANES))
    l = jnp.broadcast_to(l[..., None], (*l.shape, NUM_LANES))
    # as the library has it: the kernel reads the first 128 lanes of a
    # column broadcast to the key tile's width (ROADMAP Speed 4b)
    di = jnp.broadcast_to(di[..., None], (*di.shape, block_k))

    n_k = L // block_k

    def q_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki, run_ref, block_ref):
        return (b, h, block_ref[b, qi * n_k + ki], 0)

    def qseg_map(b, h, qi, ki, *_):
        return (b, qi, 0)

    def kseg_map(b, h, qi, ki, run_ref, block_ref):
        return (b, 0, block_ref[b, qi * n_k + ki])

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), kv_map)
    col_spec = pl.BlockSpec((1, 1, block_q, NUM_LANES), q_map)
    qseg, kseg = _segment_operands(segment_ids)
    return pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # skip_operands
            grid=(G, H, L // block_q, L // block_k),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, block_q, NUM_LANES), qseg_map),
                pl.BlockSpec((1, NUM_SUBLANES, block_k), kseg_map),
                col_spec, col_spec, q_spec, col_spec,
            ],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        name=f"flash_mha_bwd_dq_block_q_major_{block_q}_block_k_major_{block_k}_block_k_{block_k}",
        interpret=interpret,
    )(*prefetch, q, k, v, qseg, kseg, l, m, do, di)


# ---------------------------------------------------------------------------
# the differentiable call
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_mha(q, k, v, segment_ids, skips: FlashSkips, save_residuals: bool, sm_scale: float, blocks: FlashBlocks, interpret: bool):
    return _flash_fwd(
        q, k, v, segment_ids, skips.fwd, save_residuals=save_residuals, sm_scale=sm_scale,
        block_q=blocks.fwd[0], block_k=blocks.fwd[1], interpret=interpret,
    )


def _flash_mha_fwd(q, k, v, segment_ids, skips, save_residuals, sm_scale, blocks, interpret):
    if save_residuals:
        raise NotImplementedError("Higher-order AD not supported")
    o, l, m = _flash_mha(q, k, v, segment_ids, skips, True, sm_scale, blocks, interpret)
    return o, (q, k, v, segment_ids, skips, o, l, m)


def _flash_mha_bwd(save_residuals, sm_scale, blocks, interpret, residuals, do):
    if save_residuals:
        raise NotImplementedError("Higher-order AD not supported")
    q, k, v, segment_ids, skips, o, l, m = residuals
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)  # [G, H, L]
    dk, dv = _flash_bwd_dkv(
        q, k, v, segment_ids, skips.dkv, l, m, do, di,
        sm_scale=sm_scale, block_q=blocks.dkv[0], block_k=blocks.dkv[1], interpret=interpret,
    )
    dq = _flash_bwd_dq(
        q, k, v, segment_ids, skips.dq, l, m, do, di,
        sm_scale=sm_scale, block_q=blocks.dq[0], block_k=blocks.dq[1], interpret=interpret,
    )
    return dq, dk, dv, None, None


_flash_mha.defvjp(fwd=_flash_mha_fwd, bwd=_flash_mha_bwd)


# jitted, as the library's entry point is: the kernels' names then reach a
# device trace as they are written here, not wrapped in the caller's
# transformations (transpose(jvp(...)))
@functools.partial(jax.jit, static_argnames=("sm_scale", "blocks", "interpret"))
def flash_mha(q, k, v, segment_ids, skips: FlashSkips, *, sm_scale: float, blocks: FlashBlocks, interpret: bool = False):
    """Causal attention within segments, differentiable in q, k, v.
    q, k, v: ``[G, H, L, d]`` with as many KV heads as query heads;
    ``segment_ids`` ``[G, L]`` int32; ``skips`` from ``flash_skips`` for
    the same ids and ``blocks``. ``interpret=True`` runs the kernels
    through the Pallas interpreter (CPU tests, tools/kernelcheck.py)."""
    G, H, L, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q, k, v must share one shape: {q.shape}, {k.shape}, {v.shape}")
    if segment_ids.shape != (G, L):
        raise ValueError(f"segment ids {segment_ids.shape} do not match q's [G, L]={[G, L]}")
    if d % NUM_LANES:
        raise NotImplementedError(f"head_dim={d} must be a multiple of {NUM_LANES}")
    return _flash_mha(q, k, v, segment_ids, skips, False, sm_scale, blocks, interpret)
