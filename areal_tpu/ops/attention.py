"""Attention dispatch for packed [G, L] grids: XLA sdpa, Pallas flash, ring.

Replaces the reference's flash-attn dependency (SURVEY §2.8.4). Three impls:

- ``xla``: masked einsum+softmax — XLA fuses/tiles onto the MXU; reference
  numerics for tests and the CPU mesh.
- ``pallas``: TPU flash attention. Training uses the kernels of
  ``ops/flash_kernels.py`` (forward, dK/dV, dQ and their custom VJP: a fork
  of jax's library kernels that also skips the tiles of a packed row that
  hold no same-segment pair); the forward-only hot path (logprob recompute,
  ref/prox forward) uses the leaner forward kernel below
  (``flash_fwd_pallas``), which skips the same tiles. Packed-segment +
  causal masking via segment ids/col-index — same semantics as the grid
  mask. Both take their tile edges from ``flash_tiles(L, head_dim)``: at
  edges of 128 a kernel pays for tens of thousands of near-empty grid steps
  a layer. The chosen tiles are logged once per shape and written into the
  kernels' names, which a device trace shows.
- ring attention lives in parallel/ring_attention.py (context parallelism).

All entry points take [G, L, H, d] (model layout) and handle the transpose
to the kernels' [G, H, L, d].
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops import flash_kernels
from areal_tpu.ops.flash_kernels import FlashBlocks
from areal_tpu.utils import logging as alog

logger = alog.getLogger("attention")


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
# the dq wrapper broadcasts the row sums of dO*O to a float32
# [G, H, L, block_k] in HBM before the kernel runs: past 512 that costs
# more than the kernel gains (2.39 + 0.46 ms a layer at 512, 1.95 + 1.03 at 1024)
_DQ_EDGE_CAP = 512


class FlashTiles(NamedTuple):
    """Tile edge (query and key axes alike) of each of the three training
    kernels."""

    fwd: int
    dkv: int
    dq: int


def flash_tiles(L: int, head_dim: int) -> FlashTiles:
    """Tiles from what the call can see: for each kernel the largest edge
    that divides the row length, capped by what VMEM holds at this
    head_dim; 128 at worst."""
    cap = FLASH_TILE_EDGES[0] * 128 // max(head_dim, 128)

    def edge(at_most: int) -> int:
        return next((e for e in FLASH_TILE_EDGES if e <= at_most and L % e == 0), 128)

    return FlashTiles(fwd=edge(cap), dkv=edge(cap), dq=edge(min(cap, _DQ_EDGE_CAP)))


def flash_block_sizes(tiles: FlashTiles) -> FlashBlocks:
    """What ``flash_kernels.flash_mha`` takes: each kernel's (query, key)
    edges, both ``tiles``' edge for that kernel."""
    return FlashBlocks(*((edge, edge) for edge in tiles))


@functools.lru_cache(maxsize=None)
def _log_tiles(shape: tuple, blocks: FlashBlocks) -> None:
    # cached: one line per (shape, tiles), however often it is traced
    logger.info(f"flash_train at [G, L, H, d]={list(shape)}: tiles {blocks._asdict()}")


@functools.partial(jax.tree_util.register_dataclass, data_fields=["segment_ids", "skips"], meta_fields=["blocks"])
@dataclasses.dataclass(frozen=True)
class FlashMask:
    """What ``flash_train`` masks and skips by: a grid's segment ids, the
    kernels' tile edges (static) and their skip tables for both."""

    segment_ids: jax.Array
    skips: flash_kernels.FlashSkips
    blocks: FlashBlocks


def flash_mask(segment_ids, head_dim: int, block_sizes: FlashBlocks | None = None) -> FlashMask:
    """``flash_train``'s mask for ``segment_ids`` [G, L]. A model builds it
    once a forward pass, outside its scan over layers: the skip tables are
    a few dozen tiny ops that XLA would otherwise run again in every layer,
    forward, recomputed and backward. ``block_sizes`` is for the probe's
    sweep; the program leaves it to ``flash_tiles``."""
    if block_sizes is None:
        block_sizes = flash_block_sizes(flash_tiles(segment_ids.shape[-1], head_dim))
    # the barrier keeps the tables where they are built: XLA otherwise sinks
    # their last cheap ops back into the loop over layers
    skips = jax.lax.optimization_barrier(flash_kernels.flash_skips(segment_ids, block_sizes))
    return FlashMask(segment_ids, skips, block_sizes)


def flash_train(q, k, v, mask: FlashMask, interpret: bool = False):
    """Differentiable flash attention (``ops/flash_kernels.py``: causal +
    segment masking, tiles without a same-segment pair skipped). q,k,v:
    [G, L, H, d] with kv heads pre-replicated; ``mask`` from
    ``flash_mask``. ``interpret=True`` runs the kernels through the Pallas
    interpreter (CPU tests, tools/kernelcheck.py)."""
    _log_tiles(tuple(q.shape), mask.blocks)
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    out = flash_kernels.flash_mha(
        qt, kt, vt, mask.segment_ids, mask.skips, sm_scale=q.shape[-1] ** -0.5, blocks=mask.blocks, interpret=interpret
    )
    return jnp.transpose(out, (0, 2, 1, 3))


@functools.lru_cache(maxsize=None)
def _log_tile_counts(shape: tuple, counts: tuple) -> None:
    # cached: one line per (shape, counts); a layout that runs other tiles logs again
    logger.info(f"flash_train at [G, L]={list(shape)}: tiles run of causal tiles {dict(counts)}")


def flash_tile_counts(segment_ids, blocks: FlashBlocks) -> dict[str, tuple[int, int]]:
    """{kernel: (tiles run, causal tiles)} of ``flash_train`` over a grid's
    rows, a head and a layer: how far the segment skip engages. On the
    host, from numpy ``segment_ids`` [G, L]."""
    G, L = segment_ids.shape
    by_edges = {
        edges: (int(flash_kernels.live_tiles(segment_ids, *edges).sum()), G * int(flash_kernels.causal_tiles(L, *edges).sum()))
        for edges in set(blocks)
    }
    counts = {kernel: by_edges[edges] for kernel, edges in blocks._asdict().items()}
    _log_tile_counts(tuple(segment_ids.shape), tuple((k, "%d/%d" % v) for k, v in counts.items()))
    return counts


# ---------------------------------------------------------------------------
# our own Pallas forward kernel (no-grad paths: logprob recompute, prefill)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    run_ref,  # scalar prefetch: flash_kernels.skip_operands
    block_ref,
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
    del block_ref
    g = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-future kv blocks (causal) and blocks that share no segment
    # id with the query block: both contribute exact zeros
    @pl.when(run_ref[g, iq * pl.num_programs(3) + ik] != 0)
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
    pre-replicated); segment_ids [G, L]. Causal by column index; a tile
    without a same-segment pair is skipped, and fetches nothing, as in
    ``flash_train``'s kernels. Tiles default to ``flash_tiles``' forward
    edge, as ``flash_train``'s do.
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
    prefetch = flash_kernels.skip_operands(segment_ids, blk_q, blk_k, outer="q")
    n_k = L // blk_k
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # skip_operands
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, blk_q, 128), lambda g, h, iq, ik, *_: (g, iq, 0)),
                pl.BlockSpec((1, 8, blk_k), lambda g, h, iq, ik, run, block: (g, 0, block[g, iq * n_k + ik])),
                pl.BlockSpec((1, 1, blk_q, d), lambda g, h, iq, ik, *_: (g, h, iq, 0)),
                pl.BlockSpec((1, 1, blk_k, d), lambda g, h, iq, ik, run, block: (g, h, block[g, iq * n_k + ik], 0)),
                pl.BlockSpec((1, 1, blk_k, d), lambda g, h, iq, ik, run, block: (g, h, block[g, iq * n_k + ik], 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda g, h, iq, ik, *_: (g, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((blk_q, 128), jnp.float32),
                pltpu.VMEM((blk_q, 128), jnp.float32),
                pltpu.VMEM((blk_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((G, H, L, d), q.dtype),
        name="flash_fwd",
        interpret=interpret,
    )(*prefetch, seg_q_in, seg_k_in, qt, kt, vt)
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


