"""Ring attention over the mesh "seq" axis (context parallelism).

The reference delegates ring/context attention to TransformerEngine inside
Megatron (megatron_utils/packed_context_parallel.py:9-173); here it is a
first-class shard_map kernel: K/V shards rotate around the ring via
``ppermute`` while each device folds one block per step into a flash-style
running softmax (fp32 max/sum carries). Causal + packed-segment masking uses
explicit global column indices, so any sequence layout works — including the
reference's 2-chunks-per-rank causal load balancing (``zigzag_indices``).

Complements Ulysses (models/qwen.py head<->seq all-to-all): Ulysses is
cheaper up to num_heads ways; ring scales context beyond head count with
O(L/sp) memory per device.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import get_abstract_mesh


def _block_attn(q, k, v, seg_q, seg_k, idx_q, idx_k, scale):
    """One q-shard × kv-block flash update ingredients.

    q: [B, Lq, H, d]; k/v: [B, Lk, H, d]. Returns (logits-masked, mask).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = (
        (seg_q[:, :, None] == seg_k[:, None, :])
        & (seg_q[:, :, None] != 0)
        & (idx_q[:, :, None] >= idx_k[:, None, :])
    )[:, None]  # [B, 1, Lq, Lk]
    return jnp.where(mask, logits, -jnp.inf)


def _ring_shard_fn(q, k, v, seg, idx, axis_name: str, scale: float, vary_axes=()):
    """Per-device body under shard_map. All inputs are local shards:
    q/k/v [B, Lc, H, d], seg/idx [B, Lc]."""
    sp = axis_size(axis_name)
    B, Lc, H, d = q.shape

    def step(i, carry):
        o, m, l, k_cur, v_cur, seg_cur, idx_cur = carry
        logits = _block_attn(q, k_cur, v_cur, seg, seg_cur, idx, idx_cur, scale)
        m_blk = jnp.max(logits, axis=-1)  # [B, H, Lq]
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (exp(-inf - -inf))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(logits - m_safe[..., None])  # [B, H, Lq, Lk]
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        perm = [(j, (j - 1) % sp) for j in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        seg_nxt = jax.lax.ppermute(seg_cur, axis_name, perm)
        idx_nxt = jax.lax.ppermute(idx_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt, seg_nxt, idx_nxt)

    # initial accumulators must carry the same varying-manual-axes type as
    # the loop outputs (which depend on mesh-varying q/k/v)
    axes = tuple(vary_axes) or (axis_name,)

    def _vary(x):
        return jax.lax.pcast(x, axes, to="varying")

    o0 = _vary(jnp.zeros((B, H, Lc, d), jnp.float32))
    m0 = _vary(jnp.full((B, H, Lc), -jnp.inf, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, Lc), jnp.float32))
    o, m, l, *_ = jax.lax.fori_loop(0, sp, step, (o0, m0, l0, k, v, seg, idx))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)  # [B, Lq, H, d]


def ring_attention(
    q: jax.Array,  # [B, L, H, d] (sharded over mesh "seq" on L)
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,  # [B, L] (0 = padding)
    col_index: jax.Array,  # [B, L] global row-column index (causality)
    mesh=None,
    axis_name: str = "seq",
    batch_axes=("data", "fsdp"),
) -> jax.Array:
    """Context-parallel causal attention for packed grids. Call inside jit
    with a mesh context; outside a mesh it falls back to single-device."""
    mesh = mesh or get_abstract_mesh()
    if axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        scale = q.shape[-1] ** -0.5
        logits = _block_attn(q, k, v, segment_ids, segment_ids, col_index, col_index, scale)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.exp(logits - m)
        o = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
        o = o / jnp.maximum(p.sum(-1), 1e-30)[..., None]
        return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)

    scale = q.shape[-1] ** -0.5
    import math

    bdeg = math.prod(mesh.shape[a] for a in batch_axes if a in mesh.shape)
    batch_spec = batch_axes if bdeg > 1 and q.shape[0] % bdeg == 0 else None
    spec_qkv = P(batch_spec, axis_name, None, None)
    spec_tok = P(batch_spec, axis_name)
    vary_axes = (axis_name,) + (tuple(batch_axes) if batch_spec else ())
    fn = shard_map(
        partial(
            _ring_shard_fn, axis_name=axis_name, scale=scale, vary_axes=vary_axes
        ),
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_tok, spec_tok),
        out_specs=spec_qkv,
    )
    return fn(q, k, v, segment_ids, col_index)


def zigzag_indices(L: int, sp: int) -> np.ndarray:
    """Causal load-balanced layout (reference packed_context_parallel.py:9-60):
    split [0, L) into 2·sp chunks; device r gets chunks (r, 2sp−1−r). Returns
    the permutation ``perm`` such that ``x[..., perm, :]`` lays tokens out in
    device order; invert with ``np.argsort(perm)``."""
    assert L % (2 * sp) == 0, (L, sp)
    c = L // (2 * sp)
    chunks = [np.arange(i * c, (i + 1) * c) for i in range(2 * sp)]
    order = []
    for r in range(sp):
        order.append(chunks[r])
        order.append(chunks[2 * sp - 1 - r])
    return np.concatenate(order)
