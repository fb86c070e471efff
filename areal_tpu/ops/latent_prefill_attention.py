"""The prompt pass's latent (MLA) attention: the PLAIN form's running
softmax of one block of queries over the key blocks up to its diagonal, ONE
Pallas launch a query block (``mla_prefill_flash``).

``models/hybrid.py mla_prefill_attend`` makes every head's key and value
from the prompt's own latent and walks a block of queries over the key
blocks with a running softmax. As XLA einsums every (query block, key
block) step writes [H, queries, keys] float32 logits to HBM and passes over
them again for the mask, the maximum, the exponential, the sum and the
cast (134 MB a step at 64 heads x 256 x 2,048: the step is bound by those
bytes, an eighth of the MXU's peak; PERF.md, PR 40). Here the logits of a
(head, key block) step live in VMEM; the arithmetic is that loop's term by
term: float32 logits from the operands' own type, ``x sm_scale``, the mask
by ``where`` to the same ``-1e30``, running maximum, sum and accumulator in
float32 scratch, the probabilities cast to the values' type before the
second product, ``acc / l`` at the last key block.

Layout, all two-dimensional and lane-aligned so that a head is a column
block (``prefill_operands`` builds it; ``key_lanes`` = qk_nope + qk_rope in
whole 128-lane tiles):

  - ``q``   [queries, H * key_lanes]: a head's ``[q_nope | q_rope | 0]``.
  - ``kv``  [L, H * (key_lanes + v)]: a head's ``[k_nope | 0 | v]`` as the
    latent's up-projection writes it when ``W_kvb``'s columns are laid out
    so (``padded_w_kvb``: once a layer, 29 MB): the rotary key's lanes hold
    zeros, never a per-head copy of it.
  - ``k_r`` [L, key_lanes]: ``[0 | k_r | 0]``, ONE row a token for every
    head; a step adds it to the head's key block in VMEM (x + 0: exact), so
    the logits are one product over ``key_lanes``.
  - ``mask`` [queries, L] int8 (the index's selection, one plane for every
    head) or none: the causal mask is then made inside from positions.

Grid (heads, key blocks); a key block past the query block's diagonal is
not visited: its step computes nothing and its operands' block index stays
on the last visited block, so it issues no DMA (as ``flash_kernels.py``
redirects skipped tiles).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

_MASK_VALUE = -1e30  # mla_prefill_attend's
_LANES = 128
_TRANS_B = (((1,), (1,)), ((), ()))


def key_lanes(qk_nope: int, qk_rope: int) -> int:
    """A head's key width in whole lane tiles."""
    return -(-(qk_nope + qk_rope) // _LANES) * _LANES


def padded_w_kvb(w_kvb, heads: int, qk_nope: int, qk_rope: int):
    """``W_kvb`` [rank, H * (nope + v)] with a head's columns as ``[k_nope |
    0 | v]`` [rank, H * (key_lanes + v)]: the latent's up-projection then
    writes the launch's ``kv`` operand as it stands."""
    rank = w_kvb.shape[0]
    w = w_kvb.reshape(rank, heads, -1)
    gap = jnp.zeros((rank, heads, key_lanes(qk_nope, qk_rope) - qk_nope), w.dtype)
    return jnp.concatenate([w[..., :qk_nope], gap, w[..., qk_nope:]], axis=-1).reshape(rank, -1)


def prefill_operands(q_nope, q_rope, k_r):
    """(q [T, H * key_lanes], k_r [L, key_lanes]) of the launch from a
    block's queries [T, H, nope], [T, H, rope] and the prompt's rotary key
    [L, rope]."""
    T, H, dn = q_nope.shape
    dr = q_rope.shape[-1]
    pad = key_lanes(dn, dr) - dn - dr
    q = jnp.concatenate([q_nope, q_rope] + ([jnp.zeros((T, H, pad), q_nope.dtype)] if pad else []), axis=-1)
    return q.reshape(T, -1), jnp.pad(k_r, ((0, 0), (dn, pad)))


def _kernel(
    meta_ref,  # SMEM [2] int32: the block's first query position, key blocks up to its diagonal
    q_ref,  # VMEM [tq, dk]: the head's queries
    kv_ref,  # VMEM [tk, dk + dv]: the head's [k_nope | 0 | v] of this key block
    kr_ref,  # VMEM [tk, dk]: [0 | k_r | 0] of this key block
    *refs,  # with a selection: VMEM [tq, tk] int8; then o_ref VMEM [tq, dv]; m, l VMEM [tq, 1] f32; acc VMEM [tq, dv] f32
    sm_scale: float,
    masked: bool,
):
    mask_ref = refs[0] if masked else None
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    kb = pl.program_id(1)
    tq, dk = q_ref.shape
    tk = kv_ref.shape[0]

    @pl.when(kb == 0)
    def _start():
        m_scr[...] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(kb < meta_ref[1])
    def _attend():
        kvb = kv_ref[...]
        k = (kvb[:, :dk].astype(jnp.float32) + kr_ref[...].astype(jnp.float32)).astype(kvb.dtype)  # x + 0: exact
        logits = jax.lax.dot_general(q_ref[...], k, _TRANS_B, preferred_element_type=jnp.float32)
        if masked:
            seen = mask_ref[...] != 0
        else:
            pos = meta_ref[0] + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            seen = pos >= kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        logits = jnp.where(seen, logits * sm_scale, _MASK_VALUE)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # a block may hold no key the row sees: the mask value is then its maximum, and exp(0) must not count
        p = jnp.where(seen, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(p.astype(kvb.dtype), kvb[:, dk:], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(kb == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _log_launch(heads: int, L: int, tq: int, tk: int, masked: bool) -> None:
    # cached: one line per shape, however often it is traced
    n_q, n_k = L // tq, L // tk
    visited = sum(-(-(i + 1) * tq // tk) for i in range(n_q))
    logger.info(
        f"mla_prefill_flash at [H, L]={[heads, L]}: {tq} queries x {tk} keys a block, "
        f"{visited} key blocks visited of {n_k} x {n_q}, {'the selection as a mask operand' if masked else 'causal mask inside'}"
    )


def mla_prefill_flash(
    q: jax.Array,  # [tq, H * dk]: a block's queries, a head's [q_nope | q_rope | 0]
    kv: jax.Array,  # [L, H * (dk + dv)]: a head's [k_nope | 0 | v]
    k_r: jax.Array,  # [L, dk]: [0 | k_r | 0], every head's
    block: jax.Array,  # scalar int32: which block of tq queries of the prompt these are
    mask: jax.Array | None = None,  # bool [tq, L]: the keys each query attends to; none: every key up to the query
    *,
    heads: int,
    block_k: int,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """softmax over the keys a query sees of (q . [k_nope + k_r]) x sm_scale,
    times v: [tq, H * dv] in ``kv``'s type, for queries ``block * tq ...
    (block + 1) * tq - 1`` of a prompt of L tokens. With ``mask`` a query
    sees what its row marks (every row at least one key, none past the
    block's diagonal: ``select_top`` over the visible keys gives that)."""
    tq, L, H = q.shape[0], kv.shape[0], heads
    dk = q.shape[1] // H
    dv = kv.shape[1] // H - dk
    tk = block_k
    if dk % _LANES or dv % _LANES or dv <= 0 or k_r.shape != (L, dk):
        raise ValueError(f"q {list(q.shape)}, kv {list(kv.shape)}, k_r {list(k_r.shape)}: {H} heads of whole lane tiles expected")
    if L % tk or L % tq or tk % _LANES or tq % 32:
        raise ValueError(f"blocks of {tq} queries x {tk} keys do not tile a prompt of {L}")
    _log_launch(H, L, tq, tk, mask is not None)
    n_kb = L // tk
    first = jnp.asarray(block, jnp.int32) * tq
    meta = jnp.stack([first, (first + tq + tk - 1) // tk])  # key blocks up to the diagonal

    def visited(kb, meta_ref):  # a block past the diagonal stays on the last one visited: no DMA of its own
        return jnp.minimum(kb, meta_ref[1] - 1)

    in_specs = [
        pl.BlockSpec((tq, dk), lambda h, kb, meta_ref: (0, h)),
        pl.BlockSpec((tk, dk + dv), lambda h, kb, meta_ref: (visited(kb, meta_ref), h)),
        pl.BlockSpec((tk, dk), lambda h, kb, meta_ref: (visited(kb, meta_ref), 0)),
    ]
    operands = [q, kv, k_r.astype(kv.dtype)]
    if mask is not None:
        if mask.shape != (tq, L):
            raise ValueError(f"mask {list(mask.shape)} for {tq} queries of a prompt of {L}")
        in_specs.append(pl.BlockSpec((tq, tk), lambda h, kb, meta_ref: (0, visited(kb, meta_ref))))
        operands.append(mask.astype(jnp.int8))
    item = kv.dtype.itemsize
    # the operands' blocks twice (the pipeline's two buffers), the scratch, and a tile's float32 logits,
    # probabilities and their cast as the compiler may hold them at once
    vmem = 2 * (tq * dk + tk * (2 * dk + dv) + tq * dv) * item + 2 * tq * tk * (mask is not None)
    vmem += tq * (2 * _LANES + dv) * 4 + tq * tk * 14
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=float(sm_scale), masked=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, n_kb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tq, dv), lambda h, kb, meta_ref: (0, h)),
            scratch_shapes=[
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tq, H * dv), kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=min(100 << 20, max(32 << 20, vmem + (8 << 20)))
        ),
        # the key blocks up to the diagonal of a middle block: what XLA schedules the neighbours by
        cost_estimate=pl.CostEstimate(
            flops=H * tq * L * (dk + dv), transcendentals=H * tq * L // 2, bytes_accessed=(kv.size + q.size * 2) * item
        ),
        name="mla_prefill_flash",
        interpret=interpret,
    )(meta, *operands)
