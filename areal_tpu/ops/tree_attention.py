"""Tree attention phase 2: Pallas block-sparse ancestor-bitmask kernel.

Reference: areal/models/tree_attn/triton_kernel.py (1,037 LoC) — the
reference's main custom kernel. Packed trie nodes attend only their root
path; the mask is shipped as PACKED BITS (32 nodes per uint32 word, vs the
reference's 64-bit words — TPU lanes are 32-bit) and expanded in-register
inside the kernel, and whole [BQ, BK] tiles with no ancestor relation are
skipped via a host-computed block map — attention FLOPs and mask memory
scale with the trie's structure instead of N².

Layout, chosen for the TPU's (8, 128) tiling: bits are packed along the
QUERY axis — ``mask_words[r, j]`` holds, in bit b, whether node j is an
ancestor (or self) of node 32*r + b — so the mask tile of a
[BLOCK_Q=256, BLOCK_K=128] logits tile is a dense, lane-aligned (8, 128)
block of words, expanded by a sublane broadcast and a per-row shift.
(Packing along the key axis, as the Triton reference does, gives a
(128, 4) tile: 4 lanes of 128, which the TPU lowering refuses.) The tile
skip map rides scalar prefetch (SMEM), and the per-row logsumexp residual
is stored lane-broadcast ([H, N, 128], as jax's TPU flash attention keeps
its l/m residuals).

Because the trie is built parent-before-child (models/tree.py build_tree),
ancestors satisfy j <= i: everything above the block diagonal is skipped
for free, and deep-branching tries skip most sub-diagonal tiles too.

Differentiable: ``tree_attention`` carries a custom VJP whose backward is
two more block-sparse kernels (dQ; dK/dV) sharing the same packed-bit mask
expansion and block skip map — so tree *training* pays structure-sparse
FLOPs too, matching the reference Triton kernel's fwd+bwd
(areal/models/tree_attn/triton_kernel.py). The forward kernel additionally
emits per-row logsumexp as the softmax residual (recompute-style backward,
no [N, N] probability materialization). Off-TPU the kernels run in Pallas
interpret mode so CPU tests exercise the real code; on a TPU they are
always compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256  # q tile edge, and the node-axis padding granularity
BLOCK_K = 128  # k tile edge
WORD = 32  # mask bits per uint32
_LANES = 128  # lane-broadcast width of the per-row residuals


def pack_ancestor_bits(
    parent: np.ndarray, n_pad: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side: parent pointers -> (mask_words [Npad/32, Npad] uint32,
    block_any [Npad/BLOCK, Npad/BLOCK_K] int32).

    Bit b of mask_words[r, j] is set iff j is an ancestor of node
    i = 32*r + b (or i itself); block_any[bi, bj] = 1 iff ANY (i, j) pair
    in that [BLOCK, BLOCK_K] tile is set — the kernel skips tiles where it
    is 0."""
    N = len(parent)
    n_pad = n_pad or -(-N // BLOCK) * BLOCK
    assert n_pad % BLOCK == 0 and n_pad >= N
    # each node's ancestor row, packed along the key axis (cheap to build:
    # a row is its parent's row plus its own bit) ...
    rows = np.zeros((n_pad, n_pad // WORD), np.uint32)
    for i in range(N):
        p = int(parent[i])
        if p >= 0:
            rows[i] = rows[p]
        rows[i, i // WORD] |= np.uint32(1) << np.uint32(i % WORD)
    # ... re-packed along the query axis, 32 rows at a time
    words = np.zeros((n_pad // WORD, n_pad), np.uint32)
    shifts = np.arange(WORD, dtype=np.uint32)[:, None]
    for r in range(n_pad // WORD):
        bits = np.unpackbits(
            rows[r * WORD : (r + 1) * WORD].view(np.uint8),
            axis=1,
            bitorder="little",
        )  # [32, n_pad]
        words[r] = np.bitwise_or.reduce(bits.astype(np.uint32) << shifts, axis=0)
    wpb = BLOCK // WORD  # word rows per q tile
    block_any = (
        words.reshape(n_pad // BLOCK, wpb, n_pad // BLOCK_K, BLOCK_K)
        .any(axis=(1, 3))
        .astype(np.int32)
    )
    return words, block_any


def _expand_mask(words_ref):
    """Packed words [BLOCK/32, BLOCK_K] -> [BLOCK, BLOCK_K] bool,
    in-register: each word row broadcasts down its 32 query rows (aligned
    sublane concat, no 3-D reshapes), then a per-row logical shift selects
    the bit."""
    words = words_ref[...]  # int32 (bitcast in the wrapper)
    expanded = jnp.concatenate(
        [
            jnp.broadcast_to(words[w : w + 1, :], (WORD, BLOCK_K))
            for w in range(BLOCK // WORD)
        ],
        axis=0,
    )
    row_bit = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK_K), 0) % WORD
    return (jax.lax.shift_right_logical(expanded, row_bit) & 1) > 0


def _logits(q, k, scale):
    return (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )  # [BLOCK, BLOCK_K]


def _tree_attn_kernel(
    block_any_ref,  # SMEM [nBq * nBk] int32 — tile skip map
    q_ref,  # [BLOCK, d]
    k_ref,  # [BLOCK_K, d]
    v_ref,  # [BLOCK_K, d]
    words_ref,  # [BLOCK // WORD, BLOCK_K] int32 — this tile's mask words
    o_ref,  # [BLOCK, d]
    lse_ref,  # [BLOCK, 128] fp32 — per-row logsumexp (backward residual)
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(block_any_ref[iq * nk + ik] > 0)
    def _compute():
        v = v_ref[...]
        mask = _expand_mask(words_ref)
        logits = jnp.where(mask, _logits(q_ref[...], k_ref[...], scale), -1e30)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        # per-row softmax residual for the backward, lane-broadcast
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _specs(d: int, swap: bool = False) -> dict:
    """BlockSpecs shared by the three kernels, written for a grid whose
    axes are (head, q tile, k tile); ``swap`` re-reads them for the dK/dV
    grid (head, k tile, q tile)."""

    def at(fn):
        if swap:
            return lambda h, jk, iq, *_: fn(h, iq, jk)
        return lambda h, iq, ik, *_: fn(h, iq, ik)

    return {
        "q": pl.BlockSpec((None, BLOCK, d), at(lambda h, iq, ik: (h, iq, 0))),
        "k": pl.BlockSpec((None, BLOCK_K, d), at(lambda h, iq, ik: (h, ik, 0))),
        "row": pl.BlockSpec((None, BLOCK, _LANES), at(lambda h, iq, ik: (h, iq, 0))),
        "words": pl.BlockSpec((BLOCK // WORD, BLOCK_K), at(lambda h, iq, ik: (iq, ik))),
    }


def _prep(q, mask_words, block_any):
    N, H, d = q.shape
    assert N % BLOCK == 0, (N, BLOCK)  # unpadded input would silently truncate
    assert mask_words.shape == (N // WORD, N), (mask_words.shape, N)
    return (
        N // BLOCK,
        N // BLOCK_K,
        jax.lax.bitcast_convert_type(mask_words, jnp.int32),
        block_any.reshape(-1).astype(jnp.int32),
    )


def _fwd_pallas(q, k, v, mask_words, block_any, interpret):
    N, H, d = q.shape
    nq, nk, words, skip = _prep(q, mask_words, block_any)
    qt, kt, vt = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))
    sp = _specs(d)
    out, lse = pl.pallas_call(
        functools.partial(_tree_attn_kernel, scale=d**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nq, nk),
            in_specs=[sp["q"], sp["k"], sp["k"], sp["words"]],
            out_specs=[sp["q"], sp["row"]],
            scratch_shapes=[
                pltpu.VMEM((BLOCK, _LANES), jnp.float32),
                pltpu.VMEM((BLOCK, _LANES), jnp.float32),
                pltpu.VMEM((BLOCK, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((H, N, d), q.dtype),
            jax.ShapeDtypeStruct((H, N, _LANES), jnp.float32),
        ],
        name="tree_attn_fwd",
        interpret=interpret,
    )(skip, qt, kt, vt, words)
    return jnp.transpose(out, (1, 0, 2)), lse


def _tree_bwd_dq_kernel(
    block_any_ref,  # SMEM [nBq * nBk]
    q_ref,  # [BLOCK, d]
    k_ref,  # [BLOCK_K, d]
    v_ref,  # [BLOCK_K, d]
    do_ref,  # [BLOCK, d]
    lse_ref,  # [BLOCK, 128]
    delta_ref,  # [BLOCK, 128]
    words_ref,  # [BLOCK // WORD, BLOCK_K]
    dq_ref,  # [BLOCK, d]
    dq_scr,  # VMEM [BLOCK, d] fp32
    *,
    scale: float,
):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(block_any_ref[iq * nk + ik] > 0)
    def _compute():
        k = k_ref[...]
        do = do_ref[...]
        mask = _expand_mask(words_ref)
        logits = _logits(q_ref[...], k, scale)
        p = jnp.where(mask, jnp.exp(logits - lse_ref[:, :1]), 0.0)
        dp = jax.lax.dot_general(  # [BLOCK, BLOCK_K] = dO @ V^T
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[:, :1])
        dq_scr[...] += (
            jax.lax.dot_general(
                ds.astype(k.dtype),
                k,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _tree_bwd_dkv_kernel(
    block_any_ref,  # SMEM [nBq * nBk]
    q_ref,  # [BLOCK, d]
    k_ref,  # [BLOCK_K, d]
    v_ref,  # [BLOCK_K, d]
    do_ref,  # [BLOCK, d]
    lse_ref,  # [BLOCK, 128]
    delta_ref,  # [BLOCK, 128]
    words_ref,  # [BLOCK // WORD, BLOCK_K]
    dk_ref,  # [BLOCK_K, d]
    dv_ref,  # [BLOCK_K, d]
    dk_scr,  # VMEM [BLOCK_K, d] fp32
    dv_scr,  # VMEM [BLOCK_K, d] fp32
    *,
    scale: float,
):
    # grid is (head, k tile, q tile): the reduction runs over q tiles
    jk, iq = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(1)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(block_any_ref[iq * nk + jk] > 0)
    def _compute():
        q = q_ref[...]
        do = do_ref[...]
        mask = _expand_mask(words_ref)  # [BLOCK, BLOCK_K]
        logits = _logits(q, k_ref[...], scale)
        p = jnp.where(mask, jnp.exp(logits - lse_ref[:, :1]), 0.0)
        # dV[BLOCK_K, d] = P^T @ dO — contract the query dim, no transpose
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype),
            do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[:, :1])
        dk_scr[...] += (
            jax.lax.dot_general(
                ds.astype(q.dtype),
                q,
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )

    @pl.when(iq == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _interp(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def tree_attention(
    q: jax.Array,  # [N, H, d] (N padded to BLOCK)
    k: jax.Array,
    v: jax.Array,
    mask_words: jax.Array,  # [N // 32, N] uint32, packed along the query axis
    block_any: jax.Array,  # [N // BLOCK, N // BLOCK_K] int32
    interpret: bool | None = None,
) -> jax.Array:
    """Block-sparse ancestor-masked attention -> [N, H, d]. Differentiable
    in q/k/v (custom VJP over the sparse backward kernels)."""
    out, _ = _fwd_pallas(q, k, v, mask_words, block_any, _interp(interpret))
    return out


def _tree_attn_fwd(q, k, v, mask_words, block_any, interpret):
    out, lse = _fwd_pallas(q, k, v, mask_words, block_any, _interp(interpret))
    return out, (q, k, v, out, lse, mask_words, block_any)


def _tree_attn_bwd(interpret, res, dout):
    q, k, v, out, lse, mask_words, block_any = res
    interpret = _interp(interpret)
    N, H, d = q.shape
    nq, nk, words, skip = _prep(q, mask_words, block_any)
    scale = d**-0.5
    # delta[h, i] = sum_d dO * O — the softmax-backward row correction,
    # lane-broadcast like lse
    delta = jnp.broadcast_to(
        jnp.einsum(
            "nhd,nhd->hn", dout.astype(jnp.float32), out.astype(jnp.float32)
        )[..., None],
        (H, N, _LANES),
    )
    qt, kt, vt, dot = (
        jnp.transpose(x, (1, 0, 2)) for x in (q, k, v, dout)
    )
    operands = (skip, qt, kt, vt, dot, lse, delta, words)

    def in_specs(sp):
        return [sp["q"], sp["k"], sp["k"], sp["q"], sp["row"], sp["row"], sp["words"]]

    sp = _specs(d)
    dq = pl.pallas_call(
        functools.partial(_tree_bwd_dq_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nq, nk),  # (head, q tile, reduce over k tiles)
            in_specs=in_specs(sp),
            out_specs=sp["q"],
            scratch_shapes=[pltpu.VMEM((BLOCK, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((H, N, d), q.dtype),
        name="tree_attn_bwd_dq",
        interpret=interpret,
    )(*operands)
    # dK/dV: outer loop over k tiles, reduce over q tiles
    sp = _specs(d, swap=True)
    dk, dv = pl.pallas_call(
        functools.partial(_tree_bwd_dkv_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nk, nq),
            in_specs=in_specs(sp),
            out_specs=[sp["k"], sp["k"]],
            scratch_shapes=[
                pltpu.VMEM((BLOCK_K, d), jnp.float32),
                pltpu.VMEM((BLOCK_K, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((H, N, d), k.dtype),
            jax.ShapeDtypeStruct((H, N, d), v.dtype),
        ],
        name="tree_attn_bwd_dkv",
        interpret=interpret,
    )(*operands)
    t = lambda x: jnp.transpose(x, (1, 0, 2))
    return t(dq), t(dk), t(dv), None, None


tree_attention.defvjp(_tree_attn_fwd, _tree_attn_bwd)


def forest_hidden(
    params,
    cfg,
    ids: jax.Array,  # [Npad] int32 node tokens (padding: 0)
    positions: jax.Array,  # [Npad] int32 node depths (rope positions)
    words: jax.Array,  # [Npad // 32, Npad] uint32 ancestor bitmask
    block_any: jax.Array,  # [Npad // BLOCK, Npad // BLOCK_K] int32 tile skip map
    remat: bool | None = None,
    with_aux: bool = False,  # also return the summed MoE router aux loss
) -> jax.Array:
    """Transformer forward over packed trie nodes with the block-sparse
    kernel in every layer -> final-norm hidden states [Npad, D]
    (+ aux when asked; note the load-balance statistic is over UNIQUE
    nodes, not the packed path's duplicated tokens — document, don't
    expect bitwise aux parity).

    Pure jax-array contract (jit-safe): the engine's tree-training path
    feeds host-built node/mask arrays straight through its grad jit. The
    ancestor mask isolates disjoint trees, so a whole FOREST (many tries
    packed into one node axis, models/tree.py pack_forest) runs as one
    call. Fully differentiable via tree_attention's custom VJP."""
    from areal_tpu.models import qwen

    mcfg = cfg
    n_pad = ids.shape[0]
    H, KH, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim_
    x = jnp.take(params["embed"], ids, axis=0).astype(mcfg.jax_dtype)
    positions = positions[None]

    def layer_fn(x, layer):
        h = qwen._rms_norm(x, layer["input_norm"], mcfg.rms_norm_eps)
        q = qwen._proj(mcfg, layer, "wq", h)
        k = qwen._proj(mcfg, layer, "wk", h)
        v = qwen._proj(mcfg, layer, "wv", h)
        if mcfg.attention_bias:
            q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
        q = q.reshape(n_pad, H, hd)
        k = k.reshape(n_pad, KH, hd)
        v = v.reshape(n_pad, KH, hd)
        if mcfg.qk_norm:
            q = qwen._rms_norm(q, layer["q_norm"], mcfg.rms_norm_eps)
            k = qwen._rms_norm(k, layer["k_norm"], mcfg.rms_norm_eps)
        q = qwen._rope(q[None], positions, mcfg.rope_theta)[0]
        k = qwen._rope(k[None], positions, mcfg.rope_theta)[0]
        if KH != H:
            k = jnp.repeat(k, H // KH, axis=1)
            v = jnp.repeat(v, H // KH, axis=1)
        attn = tree_attention(q, k, v, words, block_any)
        x = x + attn.reshape(n_pad, H * hd) @ layer["wo"]
        h = qwen._rms_norm(x, layer["post_attn_norm"], mcfg.rms_norm_eps)
        if mcfg.num_experts > 0:
            from areal_tpu.models.moe import moe_ffn

            ff_out, aux = moe_ffn(h[None], layer, mcfg)  # wants [G, L, D]
            return x + ff_out[0], aux
        ff = jax.nn.silu(qwen._proj(mcfg, layer, "w_gate", h)) * qwen._proj(
            mcfg, layer, "w_up", h
        )
        return x + qwen._proj(mcfg, layer, "w_down", ff), jnp.float32(0.0)

    if remat is None:
        remat = cfg.remat
    if remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, aux = jax.lax.scan(layer_fn, x, params["layers"])
    hidden = qwen._rms_norm(x, params["final_norm"], mcfg.rms_norm_eps)
    if with_aux:
        return hidden, aux.sum()
    return hidden


def tree_forward_logprobs_pallas(params, cfg, pack, remat: bool | None = None):
    """Packed-trie forward with the block-sparse kernel in every layer.
    Fully differentiable (tree_attention carries a custom VJP), so this is
    BOTH the phase-2 scoring path and the sparse *training* path
    (models/tree.py tree_train_logprobs dispatches here). ``remat``
    checkpoints each layer like the main model (defaults to cfg.remat).
    Returns node_logp [N] like tree.tree_forward_logprobs."""
    from areal_tpu.models import qwen
    from areal_tpu.models.tree import edge_logprob_index, non_root_nodes

    N = pack.n_nodes
    n_pad = -(-N // BLOCK) * BLOCK
    words_np, block_any_np = pack_ancestor_bits(pack.parent, n_pad)
    ids = np.zeros(n_pad, np.int32)
    ids[:N] = pack.tokens
    pos = np.zeros(n_pad, np.int32)
    pos[:N] = pack.depth

    hidden = forest_hidden(
        params,
        cfg,
        jnp.asarray(ids),
        jnp.asarray(pos),
        jnp.asarray(words_np),
        jnp.asarray(block_any_np),
        remat=remat,
    )
    logits = qwen.compute_logits(params, cfg, hidden[None])[0]
    logp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows, toks = edge_logprob_index(pack)
    edge_logp = logp_all[jnp.asarray(rows), jnp.asarray(toks)]
    node_logp = jnp.zeros(N, jnp.float32)
    return node_logp.at[jnp.asarray(non_root_nodes(pack))].set(edge_logp)
