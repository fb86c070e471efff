"""Mixture-of-Experts FFN with expert parallelism (qwen3-moe family).

Reference: archon MoE stack — router (experimental/models/archon/moe/
router.py), grouped experts (grouped_experts.py), token-dispatch Triton
kernels (kernels.py:1-228), ExpertParallel (expert_parallel.py:1-512).

Two dispatch strategies, selected by ``cfg.moe_dropless``:

- **dropless (default)**: sort-based grouped dispatch. Per EP shard, the
  (token, k) assignments targeting local experts are stably sorted by
  expert id and fed through ``megablox.gmm`` — jax's Pallas grouped-matmul
  TPU kernel — so every routed token is computed (no capacity drop; the
  reference's Triton token-shuffle kernels play this role,
  archon/moe/kernels.py:1-228). Combine is a segment scatter-add weighted
  by the router gates + psum over the mesh ``expert`` axis.
- **capacity**: dense one-hot dispatch/combine einsums (mesh-transformer /
  GSPMD formulation); tokens over an expert's ``capacity_factor`` buffer
  are dropped, the residual stream carries them unchanged. Cheaper mask
  bookkeeping, but wrong for training parity when routing is imbalanced.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from jax.sharding import get_abstract_mesh

from areal_tpu.utils.jax_compat import with_sharding_constraint
from areal_tpu.utils.private_api import pin_signature

# megablox gmm is a PRIVATE pallas op called positionally below; verified
# at first use, re-checked against the installed jax by arealint PVT002
_EXPECTED_GMM_PARAMS = (
    "lhs",
    "rhs",
    "group_sizes",
    "preferred_element_type",
    "tiling",
    "group_offset",
    "existing_out",
    "transpose_rhs",
    "interpret",
)


def pinned_gmm():
    """jax's megablox grouped matmul, signature-verified at first use."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return pin_signature(gmm, _EXPECTED_GMM_PARAMS)


def _shard(x, spec):
    # jax_compat's constraint drops manual axes and no-ops outside a mesh —
    # a raw jax.lax.with_sharding_constraint here dies at lowering inside
    # the EP shard_map region, whose axes are Manual (arealint MSH003)
    return with_sharding_constraint(x, spec)


def moe_ffn(h: jax.Array, layer: dict, cfg) -> tuple[jax.Array, jax.Array]:
    """MoE feed-forward. h: [G, L, D] (post-attn-norm hidden states).

    Returns (out [G, L, D], aux_loss scalar). aux is the switch-style load
    balance loss E * sum_e(frac_e * mean_prob_e); callers weight it with
    cfg.router_aux_coef. Dispatch strategy per ``cfg.moe_dropless``."""
    if getattr(cfg, "moe_dropless", False):
        return moe_ffn_dropless(h, layer, cfg)
    return _moe_ffn_capacity(h, layer, cfg)


def _moe_ffn_capacity(h: jax.Array, layer: dict, cfg) -> tuple[jax.Array, jax.Array]:
    from areal_tpu.models.qwen import BATCH_AXES

    G, L, D = h.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = int(max(K, round(cfg.capacity_factor * K * L / E)))
    C = min(C, L)

    # --- routing (fp32 for numerics) ---
    router_logits = (h.astype(jnp.float32) @ layer["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)  # [G, L, E]
    top_p, top_e = jax.lax.top_k(probs, K)  # [G, L, K]
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- capacity assignment ---
    # one-hot expert choice per (token, k): [G, L, K, E]
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
    # position of each (token, k) in its expert's buffer: cumsum over the
    # flattened (L, K) order so primary choices of earlier tokens win slots
    flat = onehot.reshape(G, L * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat  # [G, L*K, E] slot index if chosen
    pos = (pos * flat).sum(-1).reshape(G, L, K).astype(jnp.int32)  # [G, L, K]
    within = pos < C
    gate = top_p * within  # dropped tokens contribute nothing

    # dispatch [G, L, E, C] — combine one-hot expert and one-hot slot
    slot_oh = jax.nn.one_hot(pos, C, dtype=h.dtype)  # [G, L, K, C]
    disp = jnp.einsum("glke,glkc->glec", onehot.astype(h.dtype), slot_oh)
    comb = jnp.einsum(
        "glke,glkc,glk->glec", onehot.astype(h.dtype), slot_oh, gate.astype(h.dtype)
    )

    # --- expert computation (EP over the mesh "expert" axis) ---
    xs = jnp.einsum("glec,gld->gecd", disp, h)
    xs = _shard(xs, P(BATCH_AXES, "expert", None, None))
    g1 = jnp.einsum("gecd,edf->gecf", xs, layer["we_gate"])
    u1 = jnp.einsum("gecd,edf->gecf", xs, layer["we_up"])
    y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g1) * u1, layer["we_down"])
    y = _shard(y, P(BATCH_AXES, "expert", None, None))
    out = jnp.einsum("glec,gecd->gld", comb, y)
    out = _shard(out, P(BATCH_AXES, "seq", None))

    # --- load-balance aux (switch-transformer form) ---
    frac_tokens = onehot.reshape(G, L * K, E).mean(axis=(0, 1))  # routed frac
    mean_prob = probs.mean(axis=(0, 1))
    aux = (frac_tokens * mean_prob).sum() * E
    return out.astype(h.dtype), aux.astype(jnp.float32)


def _router(h32, w_router, K: int, norm_topk: bool):
    """fp32 routing: -> (probs [T, E], top_p [T, K], top_e [T, K])."""
    logits = h32 @ w_router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    if norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def moe_ffn_dropless(h: jax.Array, layer: dict, cfg) -> tuple[jax.Array, jax.Array]:
    """Sort-based dropless MoE dispatch over the mesh ``expert`` axis.

    Inside a shard_map block (token shard x expert shard), the (token, k)
    assignments hitting this shard's experts are stably sorted by local
    expert id, run through grouped matmuls (``megablox.gmm`` — interpret
    mode off-TPU, so CPU tests exercise the same code), and scattered back
    with their gates; a psum over "expert" assembles each token's K expert
    outputs. Every assignment is computed — token conservation is exact
    (tests/test_moe.py::test_dropless_token_conservation).

    Expert weights enter the block gathered over (fsdp, model) — the
    zero-3 per-use gather shard_map's in_specs perform; TP *within* expert
    FFNs is not sharded on this path (EP takes the expert axis; meshes
    that want both should use the capacity path)."""
    from areal_tpu.models.qwen import BATCH_AXES

    gmm = pinned_gmm()

    G, L, D = h.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    axes = dict(get_abstract_mesh().shape)  # empty outside a mesh context
    e_sz = axes.get("expert", 1)
    d_sz = max(axes.get("data", 1) * axes.get("fsdp", 1), 1)
    s_sz = max(axes.get("seq", 1), 1)
    # shard_map needs every sharded dim divisible by its axes. Routing is
    # per-token, so an unshardable (G, L) layout (the tree-training
    # forest's [1, N, D]) can be RESHAPED to a shardable one when the
    # total token count divides — same math, shards keep their FLOP share
    orig_GL = None
    if (
        bool(axes)
        and not (G % d_sz == 0 and L % s_sz == 0)
        and (G * L) % (d_sz * s_sz) == 0
    ):
        orig_GL = (G, L)
        h = h.reshape(d_sz, (G * L) // d_sz, D)
        G, L = h.shape[0], h.shape[1]
    in_mesh = (
        bool(axes)
        and E % max(e_sz, 1) == 0
        and G % d_sz == 0
        and L % s_sz == 0
    )
    if bool(axes) and not in_mesh:
        # truly unshardable: run replicated — every device computes all
        # tokens. Loud, because on a big mesh this is a real perf cliff.
        _warn_replicated_once((G, L, d_sz, s_sz, e_sz))
    # platform decides compiled-or-interpret, nothing else: on a TPU gmm is
    # always compiled and a kernel the chip refuses is an error
    interpret = jax.default_backend() != "tpu"
    tile_m0 = 16 if interpret else 128

    def block(h_blk, wr, wg, wu, wd):
        # h_blk [G_, L_, D]; wg/wu [E_loc, D, F]; wd [E_loc, F, D]
        G_, L_, _ = h_blk.shape
        E_loc = wg.shape[0]
        T = G_ * L_
        # gmm requires its m dim (T*K) divisible by the m tile; tiny
        # per-shard token counts (decode chunks, the forest's replicated
        # fallback) take a smaller tile instead of failing. LARGE
        # non-divisible shapes also land here — warn, because a collapsed
        # m tile on a hot path is a silent perf cliff
        tm = math.gcd(T * K, tile_m0)
        if T * K >= tile_m0 and tm < tile_m0:
            _warn_small_tile_once((T, K, tm, tile_m0))
        tile = (tm, 128, 128)
        x = h_blk.reshape(T, D)
        probs, top_p, top_e = _router(
            x.astype(jnp.float32), wr, K, cfg.norm_topk_prob
        )
        e0 = jax.lax.axis_index("expert") * E_loc if in_mesh else 0
        ek = top_e.reshape(T * K)
        gk = top_p.reshape(T * K)
        tok = jnp.arange(T * K, dtype=jnp.int32) // K
        local = (ek >= e0) & (ek < e0 + E_loc)
        key = jnp.where(local, ek - e0, E_loc)  # non-local sorts last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=E_loc + 1).astype(jnp.int32)
        # non-local rows sort past sum(group_sizes): gmm never computes
        # them (per-shard FLOPs stay ~1/e_sz of the fleet's). Their output
        # AND vjp-cotangent rows are uninitialized, so (a) they gather from
        # / scatter to a phantom zero token row T, keeping garbage out of
        # real tokens in both directions, and (b) every gmm output is
        # masked so garbage can't ride the elementwise ops into the
        # accumulated gradients.
        group_sizes = sizes[:E_loc]
        n_local = group_sizes.sum()
        computed = jnp.arange(T * K) < n_local
        s_tok = jnp.where(computed, tok[order], T)  # phantom row for tail
        x_ext = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
        xs = x_ext[s_tok]  # [T*K, D] grouped by local expert
        cm = computed[:, None]
        g1 = jnp.where(cm, gmm(xs, wg, group_sizes, tiling=tile, interpret=interpret), 0)
        u1 = jnp.where(cm, gmm(xs, wu, group_sizes, tiling=tile, interpret=interpret), 0)
        y = (jax.nn.silu(g1) * u1).astype(x.dtype)
        yd = jnp.where(cm, gmm(y, wd, group_sizes, tiling=tile, interpret=interpret), 0)
        gates = (gk * local)[order].astype(jnp.float32)
        contrib = yd.astype(jnp.float32) * gates[:, None]
        out = (
            jnp.zeros((T + 1, D), jnp.float32).at[s_tok].add(contrib)[:T]
        )
        if in_mesh:
            out = jax.lax.psum(out, "expert")
        # switch-style aux from the (replicated-over-expert) global routing
        onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
        frac = onehot.reshape(T * K, E).mean(0)
        mean_prob = probs.mean(0)
        aux = (frac * mean_prob).sum() * E
        if in_mesh:
            aux = jax.lax.pmean(aux, ("data", "fsdp", "seq"))
        return out.reshape(G_, L_, D).astype(h_blk.dtype), aux

    if not in_mesh:
        out, aux = block(
            h,
            layer["w_router"],
            layer["we_gate"],
            layer["we_up"],
            layer["we_down"],
        )
    else:
        out, aux = shard_map(
            block,
            in_specs=(
                P(BATCH_AXES, "seq", None),
                P(None, None),
                P("expert", None, None),
                P("expert", None, None),
                P("expert", None, None),
            ),
            out_specs=(P(BATCH_AXES, "seq", None), P()),
            # gmm's inner pallas_call carries no vma annotations; the variance
            # checker can't see through it — the psum/pmean above implement the
            # replication the out_specs promise
            check_vma=False,
        )(h, layer["w_router"], layer["we_gate"], layer["we_up"], layer["we_down"])
    if orig_GL is not None:
        out = out.reshape(*orig_GL, D)
    return out, aux.astype(jnp.float32)


_SMALL_TILE_WARNED: set = set()


def _warn_small_tile_once(key: tuple) -> None:
    if key in _SMALL_TILE_WARNED:
        return
    _SMALL_TILE_WARNED.add(key)
    from areal_tpu.utils import logging as alog

    alog.getLogger("moe").warning(
        "moe gmm m dim T*K=%s*%s is not divisible by the %s tile; running "
        "with m tile %s — pad the token count to the tile for full "
        "throughput" % (key[0], key[1], key[3], key[2])
    )


_REPLICATED_WARNED: set = set()


def _warn_replicated_once(key: tuple) -> None:
    if key in _REPLICATED_WARNED:
        return
    _REPLICATED_WARNED.add(key)
    from areal_tpu.utils import logging as alog

    alog.getLogger("moe").warning(
        "moe_ffn token layout (G=%s, L=%s) is not shardable over "
        "data*fsdp=%s, seq=%s (expert=%s); dispatch runs REPLICATED — every "
        "device computes every token. Fine for tests/tiny calls, a perf "
        "cliff on real meshes." % key
    )
