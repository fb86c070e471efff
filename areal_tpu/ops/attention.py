"""Attention dispatch for packed [G, L] grids: XLA sdpa, Pallas flash, ring.

Replaces the reference's flash-attn dependency (SURVEY §2.8.4). Three impls:

- ``xla``: masked einsum+softmax — XLA fuses/tiles onto the MXU; reference
  numerics for tests and the CPU mesh.
- ``pallas``: TPU flash attention. Training uses jax's battle-tested
  ``pallas.ops.tpu.flash_attention`` (full custom VJP); the forward-only
  hot path (logprob recompute, ref/prox forward) uses our own leaner
  forward kernel below (``flash_fwd_pallas``). Packed-segment + causal
  masking via SegmentIds/col-index — same semantics as the grid mask.
  Both take their tile edges from ``flash_tiles(L, head_dim)``: the
  library's default of 128 everywhere makes a kernel pay for tens of
  thousands of near-empty grid steps a layer. The chosen tiles are logged
  once per shape, and the library writes them into its backward kernels'
  names, which a device trace shows.
- ring attention lives in parallel/ring_attention.py (context parallelism).

All entry points take [G, L, H, d] (model layout) and handle the transpose
to the kernels' [G, H, L, d].
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.utils import logging as alog
from areal_tpu.utils.private_api import pin_signature

logger = alog.getLogger("attention")

# flash_attention is a PRIVATE pallas op we call with keyword args whose
# names (and the positional q/k/v order) a jax bump can silently change;
# verified at first use, re-checked against the installed jax by arealint
# PVT002.
_EXPECTED_FLASH_ATTENTION_PARAMS = (
    "q",
    "k",
    "v",
    "ab",
    "segment_ids",
    "causal",
    "sm_scale",
    "block_sizes",
    "debug",
)
# every field of the library's BlockSizes that flash_block_sizes fills, in the
# dataclass's own order: a bump that adds a field leaves it at a default
# nobody chose
_EXPECTED_BLOCK_SIZES_FIELDS = (
    "block_q",
    "block_k_major",
    "block_k",
    "block_b",
    "block_q_major_dkv",
    "block_k_major_dkv",
    "block_k_dkv",
    "block_q_dkv",
    "block_k_major_dq",
    "block_k_dq",
    "block_q_dq",
)


def sdpa_xla(q, k, v, mask, head_dim: int):
    """Plain XLA attention. q,k,v: [G, L, H, hd]; mask [G, 1, L, L] bool."""
    scale = head_dim**-0.5
    logits = jnp.einsum("gqhd,gkhd->ghqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("ghqk,gkhd->gqhd", probs, v)


def flash_ok(L: int, head_dim: int, block: int = 128) -> bool:
    return L % block == 0 and head_dim % 128 == 0 and L >= block


# Tile edges for the flash kernels, largest first. One grid step of a Pallas
# kernel costs about 0.35 us on a v5e whatever it holds, and a 128 x 128 tile
# holds 0.04 us of MXU work: at the library's default of 128 the kernels are
# bound by the count of grid steps (6-10% of the MXU's peak at L=4096, where
# edges of 1024 reach 46-56%). tools/flash_attn_probe.py sweeps the edges on
# the chip; its table is in PERF.md (PR 27). 2048 overflows VMEM at head_dim
# 128, as 1024 does at head_dim 256.
FLASH_TILE_EDGES = (1024, 512, 256, 128)
# the library's dq wrapper broadcasts the row sums of dO*O to a float32
# [G, H, L, block_k_major] in HBM before the kernel runs: past 512 that costs
# more than the kernel gains (2.39 + 0.46 ms a layer at 512, 1.95 + 1.03 at 1024)
_DQ_EDGE_CAP = 512


class FlashTiles(NamedTuple):
    """Tile edge (query and key axes, major and minor alike) of each of the
    library's three kernels."""

    fwd: int
    dkv: int
    dq: int


def flash_tiles(L: int, head_dim: int) -> FlashTiles:
    """Tiles from what the call can see: for each kernel the largest edge
    that divides the row length, capped by what VMEM holds at this
    head_dim; 128, the library's own default, at worst."""
    cap = FLASH_TILE_EDGES[0] * 128 // max(head_dim, 128)

    def edge(at_most: int) -> int:
        return next((e for e in FLASH_TILE_EDGES if e <= at_most and L % e == 0), 128)

    return FlashTiles(fwd=edge(cap), dkv=edge(cap), dq=edge(min(cap, _DQ_EDGE_CAP)))


def pinned_block_sizes():
    """The library's ``BlockSizes``, its fields checked against the pin."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    return pin_signature(BlockSizes, _EXPECTED_BLOCK_SIZES_FIELDS)


def flash_block_sizes(tiles: FlashTiles):
    """``BlockSizes`` with every field filled from ``tiles``."""
    return pinned_block_sizes()(
        block_q=tiles.fwd,
        block_k_major=tiles.fwd,
        block_k=tiles.fwd,
        block_b=1,
        block_q_major_dkv=tiles.dkv,
        block_k_major_dkv=tiles.dkv,
        block_k_dkv=tiles.dkv,
        block_q_dkv=tiles.dkv,
        block_k_major_dq=tiles.dq,
        block_k_dq=tiles.dq,
        block_q_dq=tiles.dq,
    )


@functools.lru_cache(maxsize=None)
def _log_tiles(shape: tuple, tiles: FlashTiles) -> None:
    # cached: one line per (shape, tiles), however often it is traced
    logger.info(f"flash_train at [G, L, H, d]={list(shape)}: tiles {tiles._asdict()}")


def flash_train(q, k, v, segment_ids, block_sizes=None):
    """Differentiable flash attention (jax pallas TPU kernel, causal +
    segment masking). q,k,v: [G, L, H, d] with kv heads pre-replicated.
    ``block_sizes`` (the library's ``BlockSizes``) is for the probe's sweep;
    the program leaves it to ``flash_tiles``."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention,
    )

    pin_signature(flash_attention, _EXPECTED_FLASH_ATTENTION_PARAMS)
    if block_sizes is None:
        tiles = flash_tiles(q.shape[1], q.shape[-1])
        _log_tiles(tuple(q.shape), tiles)
        block_sizes = flash_block_sizes(tiles)
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    seg = SegmentIds(q=segment_ids, kv=segment_ids)
    out = flash_attention(
        qt,
        kt,
        vt,
        segment_ids=seg,
        causal=True,
        sm_scale=q.shape[-1] ** -0.5,
        block_sizes=block_sizes,
    )
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# our own Pallas forward kernel (no-grad paths: logprob recompute, prefill)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    seg_q_ref,  # [1, blk_q, 128] (seg ids broadcast along lanes)
    seg_k_ref,  # [1, 8, blk_k] (seg ids broadcast along sublanes)
    q_ref,  # [1, 1, blk_q, d]
    k_ref,  # [1, 1, blk_k, d]
    v_ref,  # [1, 1, blk_k, d]
    o_ref,  # [1, 1, blk_q, d]
    m_scr,  # VMEM [blk_q, 128] running max
    l_scr,  # VMEM [blk_q, 128] running sum
    acc_scr,  # VMEM [blk_q, d] accumulator
    *,
    scale: float,
    blk_q: int,
    blk_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-future kv blocks (causal): only compute when ik*blk_k could
    # contain keys <= the last query of this block
    @pl.when(ik * blk_k <= iq * blk_q + blk_q - 1)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        logits = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [blk_q, blk_k]
        q_idx = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_idx = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        seg_q = seg_q_ref[0, :, :1]  # [blk_q, 1]
        seg_k = seg_k_ref[0, :1, :]  # [1, blk_k]
        mask = (q_idx >= k_idx) & (seg_q == seg_k) & (seg_q != 0)
        logits = jnp.where(mask, logits, -1e30)

        m_prev = m_scr[:, :1]  # [blk_q, 1]
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_fwd_pallas(
    q,
    k,
    v,
    segment_ids,
    blk_q: int | None = None,
    blk_k: int | None = None,
    interpret: bool = False,
):
    """Forward-only packed flash attention. q,k,v: [G, L, H, d] (kv heads
    pre-replicated); segment_ids [G, L]. Causal by column index. Tiles
    default to ``flash_tiles``' forward edge, as ``flash_train``'s do.
    ``interpret=True`` runs the kernel through the Pallas interpreter so
    CPU tier-1 and tools/kernelcheck.py can cover it (arealint KRN005)."""
    G, L, H, d = q.shape
    edge = flash_tiles(L, d).fwd
    blk_q, blk_k = blk_q or edge, blk_k or edge
    assert L % blk_q == 0 and L % blk_k == 0, (L, blk_q, blk_k)
    scale = d**-0.5
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))

    grid = (G, H, L // blk_q, L // blk_k)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k
    )
    # segment ids broadcast into lane/sublane dims to satisfy TPU tiling
    seg_q_in = jnp.broadcast_to(segment_ids[:, :, None], (G, L, 128))
    seg_k_in = jnp.broadcast_to(segment_ids[:, None, :], (G, 8, L))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, 128), lambda g, h, iq, ik: (g, iq, 0)),
            pl.BlockSpec((1, 8, blk_k), lambda g, h, iq, ik: (g, 0, ik)),
            pl.BlockSpec((1, 1, blk_q, d), lambda g, h, iq, ik: (g, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda g, h, iq, ik: (g, h, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda g, h, iq, ik: (g, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda g, h, iq, ik: (g, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((G, H, L, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(seg_q_in, seg_k_in, qt, kt, vt)
    return jnp.transpose(out, (0, 2, 1, 3))


# measured on a v5e, forward+backward of a layer at 12,288 tokens of 12 heads
# of 128 (tools/flash_attn_probe.py; PERF.md, PR 27): with tiles of 512 and
# up the flash kernels take 2.77 ms against XLA's fused attention's 3.42 at
# L=512, 3.66 against 6.40 at 1024, 5.35 against 12.37 at 2048, and lose at
# 256 (2.79 against 1.66). A row that 512 does not divide gets smaller tiles,
# which lose at 1024 and 2048 (tiles of 128: 12.8 and 22.0 ms): it keeps XLA
# until the [L, L] float32 logits stop fitting comfortably.
FLASH_MIN_LEN = 512  # the shortest row, and the smallest tile edge, that wins
FLASH_MIN_LEN_SMALL_TILES = 4096


def _flash_wins(L: int, head_dim: int) -> bool:
    # an edge divides L, so tiles of FLASH_MIN_LEN mean a row at least as long
    return min(flash_tiles(L, head_dim)) >= FLASH_MIN_LEN or L >= FLASH_MIN_LEN_SMALL_TILES


@functools.lru_cache(maxsize=None)
def _log_xla_instead(L: int, head_dim: int, backend: str) -> None:
    # cached: one line per (shape, backend), however often it is traced
    logger.info(
        f"attn_impl=pallas runs as xla at L={L}, head_dim={head_dim} on "
        f"{backend}: the flash kernel needs a TPU, head_dim % 128 == 0 and "
        f"L a multiple of 512 >= {FLASH_MIN_LEN} (of 128 >= "
        f"{FLASH_MIN_LEN_SMALL_TILES})"
    )


def resolve_impl(requested: str, L: int, head_dim: int) -> str:
    """Static (trace-time) choice, by platform and shape: 'pallas' only on
    a TPU, when the kernel supports the shape AND the sequence is long
    enough to win; anything else runs 'xla', logged once per shape. A
    kernel the chip's compiler refuses is an error — nothing here catches
    one. 'ring' passes through (the ring wrapper itself falls back
    off-mesh)."""
    if requested == "ring":
        return "ring"
    if requested != "pallas":
        return "xla"
    if (
        jax.default_backend() == "tpu"
        and flash_ok(L, head_dim)
        and _flash_wins(L, head_dim)
    ):
        return "pallas"
    _log_xla_instead(L, head_dim, jax.default_backend())
    return "xla"


