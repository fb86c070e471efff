"""Pallas TPU kernel: a prompt pass's attention INSIDE A BAND, grouped queries
against the keys of the last ``window`` tokens (the query's own among them),
the logits of a (query tile, key tile) in VMEM and nothing of ``[H, L, L]`` or
``[H, window, 2 * window]`` anywhere.

The prompt-pass form of a window layer's read (``models/hybrid.py
swa_attend`` is the XLA form: blocks of ``window`` queries against the block
before and their own, ``[H, window, 2 * window]`` float32 logits a block: 17
GB at 128 heads and a window of 4,096, which is why this launch is the model
there and not an optimisation; it stays the CPU path and this launch's oracle
at small windows). Query ``t`` attends keys ``s`` with ``0 <= t - s <
window``, softmax over ``q . k * sm_scale``.

The band is the GRID, not a skip table: with query and key tiles of one edge
``b``, query tile ``i`` can meet key tiles ``i - n + 1 .. i``, ``n = ceil((
window - 1) / b) + 1`` (5 at a window of 4,096 and tiles of 1,024; the oldest
and the diagonal tile are each half masked, so the launch does 5 tiles' work
for a band of 4: a reader that counts the band's pairs reads it at 80% of
what it runs at, never above). The grid is (rows, query heads, query tiles,
``n``); a step whose key tile would lie before the prompt (``i - n + 1 + j <
0``) computes nothing and, its block index clamped to the tile the next step
names, fetches nothing of its own. A key tile wholly older than ``t - window
+ 1`` for every query of the tile is never named at all.

Layout: q and the output ``[A, L, H * hd]`` as the projections leave them and
``W_o`` reads them (a ``(b, hd)`` block at ``(tile, head)``: no transpose of
the 0.5 GB a 16k prompt's queries are at 128 heads), k and v ``[A, KH, L,
hd]`` (a head's tokens contiguous; 32 MB each at 8 KV heads); query head ``h``
reads KV head ``h // (H / KH)`` by the block index: no KV head is replicated.
A padded position (past a row's prompt) is a query like any other: it sees
its own key at least, its output is finite and nobody reads it; no real query
can see it (it lies in the future).

The body binds ``jax.lax`` primitives only (PERF.md, PR 45: a ``jnp``
function of a traced value inside a kernel body is a jitted call traced
apart).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
TILE_EDGES = (1024, 512, 256, 128)  # ops/attention.py FLASH_TILE_EDGES: a grid step costs 0.35 us whatever it holds


def band_tiles(window: int, edge: int) -> int:
    """Key tiles of ``edge`` tokens a query tile of the same edge can meet
    inside a band of ``window``: the diagonal tile and ``ceil((window - 1) /
    edge)`` before it."""
    return -(-(window - 1) // edge) + 1


def tile_edge(L: int) -> int:
    """The largest tile edge that divides a row of ``L`` tokens."""
    return next((e for e in TILE_EDGES if L % e == 0), L)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float, edge: int, window: int, n_band: int):
    iq = pl.program_id(2)
    ib = pl.program_id(3)
    kt = lax.add(lax.sub(iq, n_band - 1), ib)  # the key tile this step meets; before the prompt where negative

    @pl.when(lax.eq(ib, 0))
    def _init():
        m_scr[...] = lax.full(m_scr.shape, -1e30, _F32)
        l_scr[...] = lax.full(l_scr.shape, 0.0, _F32)
        acc_scr[...] = lax.full(acc_scr.shape, 0.0, _F32)

    @pl.when(lax.ge(kt, 0))
    def _compute():
        q, k, v = q_ref[0], k_ref[0, 0], v_ref[0, 0]  # [edge, hd] each
        logits = lax.mul(lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=_F32), _F32(scale))
        # how far the key lies behind the query: q_idx - k_idx
        behind = lax.add(
            lax.sub(lax.broadcasted_iota(jnp.int32, (edge, edge), 0), lax.broadcasted_iota(jnp.int32, (edge, edge), 1)),
            lax.mul(lax.sub(iq, kt), edge),
        )
        seen = lax.bitwise_and(lax.ge(behind, 0), lax.lt(behind, window))
        logits = lax.select(seen, logits, lax.full((edge, edge), -1e30, _F32))
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(logits, (1,)), (1,)))
        # a row that sees no key of this tile keeps p = 0 (not exp(0)): its sum and its values stay what they were
        p = lax.select(seen, lax.exp(lax.sub(logits, lax.broadcast_in_dim(m_new, (edge, edge), (0, 1)))), lax.full((edge, edge), 0.0, _F32))
        corr = lax.exp(lax.sub(m_prev, m_new))
        l_new = lax.add(lax.mul(l_prev, corr), lax.expand_dims(lax.reduce_sum(p, (1,)), (1,)))
        pv = lax.dot_general(lax.convert_element_type(p, v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=_F32)
        acc = acc_scr[...]
        acc_scr[...] = lax.add(lax.mul(acc, lax.broadcast_in_dim(corr, acc.shape, (0, 1))), pv)
        m_scr[...] = lax.broadcast_in_dim(m_new, m_scr.shape, (0, 1))
        l_scr[...] = lax.broadcast_in_dim(l_new, l_scr.shape, (0, 1))

    @pl.when(lax.eq(ib, n_band - 1))
    def _finalize():  # the diagonal tile came last: every query has met its own key at least
        acc = acc_scr[...]
        o_ref[0] = lax.convert_element_type(lax.div(acc, lax.broadcast_in_dim(l_scr[:, :1], acc.shape, (0, 1))), o_ref.dtype)


def swa_prefill_flash(q, k, v, *, heads: int, window: int, sm_scale: float, edge: int | None = None, interpret: bool = False):
    """softmax(q k^T * sm_scale) v inside the band ``0 <= t - s < window``:
    q ``[A, L, heads * hd]``, k and v ``[A, KH, L, hd]`` (query head h reads
    KV head ``h // (heads / KH)``), ``L`` whole tiles of ``edge`` (default:
    the largest that divides it). Returns ``[A, L, heads * hd]`` in q's type.
    ``interpret=True`` runs the kernel through the Pallas interpreter (CPU
    tests, tools/kernelcheck.py)."""
    A, L, width = q.shape
    KH, hd = k.shape[1], k.shape[3]
    assert width == heads * hd and heads % KH == 0 and k.shape == v.shape == (A, KH, L, hd), (q.shape, k.shape, v.shape)
    edge = edge or tile_edge(L)
    assert L % edge == 0, (L, edge)
    n_band = min(band_tiles(window, edge), L // edge)
    group = heads // KH

    def key_tile(a, h, iq, ib):  # clamped: a step before the prompt names the tile the first real step names
        return (a, h // group, lax.max(iq - (n_band - 1) + ib, 0), 0)

    kernel = functools.partial(_kernel, scale=float(sm_scale), edge=edge, window=int(window), n_band=n_band)
    return pl.pallas_call(
        kernel,
        grid=(A, heads, L // edge, n_band),
        in_specs=[
            pl.BlockSpec((1, edge, hd), lambda a, h, iq, ib: (a, iq, h)),
            pl.BlockSpec((1, 1, edge, hd), key_tile),
            pl.BlockSpec((1, 1, edge, hd), key_tile),
        ],
        out_specs=pl.BlockSpec((1, edge, hd), lambda a, h, iq, ib: (a, iq, h)),
        scratch_shapes=[pltpu.VMEM((edge, 128), _F32), pltpu.VMEM((edge, 128), _F32), pltpu.VMEM((edge, hd), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((A, L, width), q.dtype),
        name="swa_prefill_flash",
        interpret=interpret,
    )(q, k, v)

