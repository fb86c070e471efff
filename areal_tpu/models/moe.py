"""Mixture-of-Experts FFN with expert parallelism.

Reference: archon MoE stack — router (experimental/models/archon/moe/
router.py), grouped experts (grouped_experts.py), token-dispatch Triton
kernels (kernels.py:1-228), ExpertParallel (expert_parallel.py:1-512).

Used by ``models/qwen.py`` (``qwen3_moe``: softmax router, experts over the
mesh ``expert`` axis) and by ``models/hybrid.py`` (a sigmoid router with a
selection bias, served on one chip). The router is data of the
configuration (``route``), never a model's name.

Two dispatch strategies, selected by ``cfg.moe_dropless``:

- **dropless (default)**: every routed token is computed (``expert_ffn``),
  in one of two forms chosen by the shapes alone (``takes_dense_form``). Up
  to ``DENSE_ROWS`` rows (a decode step, a short prompt) every local expert
  runs on every row with gate 0 for the unchosen: each weight streamed once by a plain matmul
  (or, where a full batch gives an expert a handful of rows and experts go
  untouched, the same sum over the touched experts only, their weights read
  out of the layer stack by ``ops/moe_touched_experts.py``:
  ``takes_touched_form``). Above that
  (a prefill, a train step) the (token, k) assignments targeting local
  experts are stably sorted by expert id and fed through ``megablox.gmm`` —
  jax's Pallas grouped-matmul TPU kernel, with tiles chosen from the shapes
  (``gmm_tiles``); the reference's Triton token-shuffle kernels play this
  role, archon/moe/kernels.py:1-228. Each token gathers its K outputs back
  by the inverse permutation and sums them under its gates; a psum over the
  mesh ``expert`` axis assembles the shards.
- **capacity**: dense one-hot dispatch/combine einsums (mesh-transformer /
  GSPMD formulation); tokens over an expert's ``capacity_factor`` buffer
  are dropped, the residual stream carries them unchanged. Cheaper mask
  bookkeeping, but wrong for training parity when routing is imbalanced.

Scopes (docs/observability.md): ``moe_router`` (scores, bias, top-k, gates,
load), ``moe_dispatch`` (sort, counts, gather; the dense form's gate
matrix, the touched form's list), ``moe_experts`` (the three matmuls and the gating product,
or the touched-expert launch),
``moe_combine`` (the routed form's gather back and sum).
"""

from __future__ import annotations

import math
from typing import NamedTuple

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


# an expert block's three stacked matrices: [E_loc, D, F] x 2 and [E_loc, F, D] a layer
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


class Stacked(NamedTuple):
    """A layer's leaf handed over as the whole stack ``[layers, ...]`` and the
    layer's index in it: a Pallas launch reads its layer out of the stack
    where it lies, while a slice handed to a custom call is a COPY of the
    layer (XLA fuses the slice into its own matmuls only)."""

    stack: jax.Array
    index: jax.Array


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
    probs, top_p, top_e = route(h, layer["w_router"], cfg, layer.get("router_bias"))  # [G, L, E], [G, L, K]

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


def route(x: jax.Array, w_router: jax.Array, cfg, bias: jax.Array | None = None):
    """The router, as data of the configuration: x [T, D] -> (scores [T, E],
    gates [T, K], experts [T, K]), float32.

    ``cfg.router_score`` is ``softmax`` (the default) or ``sigmoid``. The K
    experts of a token are the top-K of ``scores + bias`` where the layer
    has a selection ``bias`` (a buffer of the checkpoint, not a trained
    weight); their gates are the UNBIASED scores, divided where
    ``cfg.norm_topk_prob`` by their sum (``+ cfg.router_norm_eps`` where the
    configuration gives one), times ``cfg.routed_scaling_factor``. The
    router's matmul is float32 in name: on a TPU its bfloat16 inputs multiply
    exactly either way, and a bfloat16 matmul read the same selections to
    the digit (PERF.md, PR 30)."""
    K = cfg.num_experts_per_tok
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    if getattr(cfg, "router_score", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_p, top_e = jax.lax.top_k(scores, K)
    else:
        _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), K)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.norm_topk_prob:
        total = top_p.sum(-1, keepdims=True)
        eps = getattr(cfg, "router_norm_eps", None)
        top_p = top_p / (jnp.maximum(total, 1e-9) if eps is None else total + eps)
    scale = getattr(cfg, "routed_scaling_factor", 1.0)
    if scale != 1.0:
        top_p = top_p * scale
    return scores, top_p, top_e


# Up to this many rows, every local expert runs on every row (``_experts_dense``).
# Reading an expert's three matrices costs what ~240 rows of arithmetic on
# them cost (v5e: 197 TFLOP/s over 819 GB/s), and a decode step of a hundred
# rows touches nearly every expert anyway: there the dense form streams each
# weight once at the rate of a plain matmul (90% of 819 GB/s at 128 rows),
# with no sort, no gather and no grid of small tiles, and its 8-fold
# arithmetic hides under the bytes. Above 256 rows the arithmetic shows, but
# the routed form has a floor of its own (it copies a layer's matrices out of
# the stack for the kernel, and revisits an expert once an m tile). Measured
# on a v5e at 32 experts of [2048, 1792], top-4, ms a layer, forward only
# (tools/moe_probe, PR 30): 128 rows 0.95 dense / 4.2 routed; 512: 2.1 / 4.5;
# 1024: 3.8 / 4.0; 2048: 7.9 / 4.8. So the rule, by shape alone:
DENSE_ROWS = 1024
# ... and never more rows x local experts than that measurement had. The dense
# form's arithmetic, and the [experts, rows, width] activations a backward
# pass keeps, grow with the product: 128 local experts take it up to 256 rows
# only, so a train step's shard of a thousand rows goes through the grouped
# matmuls as it did before this rule.
DENSE_EXPERTS = 32


def takes_dense_form(rows: int, local_experts: int) -> bool:
    return rows <= DENSE_ROWS and rows * local_experts <= DENSE_ROWS * DENSE_EXPERTS


# Up to this many assignments an expert gets from a FULL batch (rows x experts a
# token over the experts the router scores), a decode step on a TPU reads the
# weights of the touched experts only (``ops/moe_touched_experts.py``: the dense
# form's arithmetic over a compacted list, one Pallas launch on the expert
# STACKS). What it can win is the share of held experts no live row chose:
# about exp(-assignments x occupancy) under an even router, more under a skewed
# one. At 3 (64 rows x top-6 over 128) a third-full batch leaves 35% untouched
# by that count and 52% in the cell (7.7 of 16 held experts touched a layer and
# step at 22 live rows, PERF.md PR 37); at 6 a tenth; at 12 and more (128 rows
# x top-4 over 32: 16; a prefill of 256 rows and more: 12-48) under 2% whatever
# the batch, and XLA's matmuls sit at 91% of the weights' bytes' time: nothing
# to skip. Measured on a v5e, us a layer (tools/moe_probe --touched, PR 38):
# 16 experts of [2048, 768] at 64 rows, 4 / 8 / 12 / 16 of 16 touched: 60.9 /
# 110.6 / 161.1 / 209.7 against XLA's 210.5 whatever was touched (76 / 83 / 86
# / 88% of the touched bytes' time; 22 live rows touching 10: 135.0 against
# 210.3, the routed form 363.0); 32 experts of [2048, 1792] at 128 rows, 8 / 16
# / 32 of 32: 248.8 / 480.2 / 945.7 against 948.1. The launch never read slower
# than XLA's form, so the constant marks where it stops winning, not a crossing:
TOUCHED_ASSIGNMENTS = 6


def takes_touched_form(rows: int, experts_per_row: int, router_experts: int, local_experts: int) -> bool:
    """Whether a step of ``rows`` rows should read only the experts its live
    rows chose: by shapes alone, beside ``takes_dense_form``."""
    return takes_dense_form(rows, local_experts) and rows * experts_per_row <= TOUCHED_ASSIGNMENTS * router_experts


def touched_list(load: jax.Array, e0, n_local: int) -> tuple[jax.Array, jax.Array]:
    """(local ids [n_local] int32 of the held experts that got a row,
    compacted to the front in their order, and how many they are) from
    ``load`` [E], the rows that chose each expert: the slice the counter of
    touched experts reads. Compares and sums over [n_local, n_local], no
    sort and no scatter: the argsort of a step's live list is 15 us on a v5e
    (PERF.md, PR 29), and this runs once a layer."""
    held = jax.lax.dynamic_slice_in_dim(load, e0, n_local) > 0
    ids = jnp.arange(n_local, dtype=jnp.int32)
    rank = jnp.sum(held[None, :] & (ids[None, :] < ids[:, None]), axis=1, dtype=jnp.int32)  # touched experts before each
    at = held[None, :] & (rank[None, :] == ids[:, None])  # [place in the list, expert]
    return jnp.sum(jnp.where(at, ids[None, :], 0), axis=1, dtype=jnp.int32), jnp.sum(held, dtype=jnp.int32)


def gmm_tiles(m: int, k: int, n: int, groups: int = 1) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``megablox.gmm`` from the shapes alone, as
    ``ops/attention.py flash_tiles`` does for flash: k and n tiles of up to
    1024 that divide the edge (the library's 128 x 128 walks one expert's
    [2048, 1792] matrix in 224 grid steps of 32 KB: 5 times slower at every
    row count, tools/moe_probe), and an m tile of 256, or 512 once a group
    has a thousand rows and more (an expert is visited once for every m tile
    its rows touch, and computes the whole tile each time: at 128 rows a
    group a tile of 512 is 3/4 waste). Double-buffered blocks stay inside
    the chip's default scoped VMEM (11 MB at 512 x 1024 x 896). gmm needs
    ``m % tm == 0``; k and n tiles that do not divide are masked."""

    def edge(size: int, cap: int = 1024) -> int:
        if size <= cap:
            return size
        fits = [t for t in range(cap, 127, -128) if size % t == 0]
        return fits[0] if fits else 128

    return math.gcd(m, 512 if m >= 1024 * groups else 256), edge(k), edge(n)


def _local_gates(top_e, gates, e0, n_local: int) -> jax.Array:
    """[T, n_local] float32: each local expert's gate for each row, 0 where
    the row did not choose it."""
    ids = e0 + jnp.arange(n_local, dtype=top_e.dtype)
    hit = top_e[:, :, None] == ids[None, None, :]
    return jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)


def _experts_dense(x, wg, wu, wd, top_e, gates, e0):
    """Every local expert on every row, the gate (0 for the unchosen) folded
    in before the down projection, which then contracts over experts and
    width at once: the exact sum, three plain matmuls."""
    E_loc = wg.shape[0]
    with jax.named_scope("moe_dispatch"):
        ge = _local_gates(top_e, gates, e0, E_loc).T  # [E_loc, T]
    with jax.named_scope("moe_experts"):
        # the rows broadcast over the experts make ``e`` a batch dimension of
        # the matmul: as "td,edf->etf" XLA:TPU wants the weights' contracted
        # dimension minor and COPIES the whole layer stack of them into that
        # layout, once a program (5.3 GB at 12 layers of 32 experts)
        xe = jnp.broadcast_to(x[None], (E_loc, *x.shape))
        g1 = jnp.einsum("etd,edf->etf", xe, wg)
        u1 = jnp.einsum("etd,edf->etf", xe, wu)
        y = jax.nn.silu(g1.astype(jnp.float32)) * u1.astype(jnp.float32) * ge[:, :, None]
        return jnp.einsum("etf,efd->td", y.astype(x.dtype), wd, preferred_element_type=jnp.float32)


def _experts_touched(x, wg: Stacked, wu: Stacked, wd: Stacked, top_e, gates, load, e0):
    """The dense form's sum over the experts that got a live row, their
    weights read out of the stacks ``[layers, E_loc, ...]`` by one Pallas
    launch; an expert with no live row has gate 0 on every row and adds
    exactly 0, so leaving it out is the same result."""
    from areal_tpu.ops.moe_touched_experts import touched_expert_ffn

    E_loc = wg.stack.shape[1]
    with jax.named_scope("moe_dispatch"):
        ge = _local_gates(top_e, gates, e0, E_loc)
        touched, n_touched = touched_list(load, e0, E_loc)
    with jax.named_scope("moe_experts"):
        return touched_expert_ffn(x, ge, wg.stack, wu.stack, wd.stack, wg.index, touched, n_touched)


def _experts_routed(x, wg, wu, wd, top_e, gates, e0, interpret: bool):
    """Sort-based dropless dispatch: the (row, k) assignments that hit a
    local expert, stably sorted by expert, through three grouped matmuls;
    each row then gathers its K outputs back by the inverse permutation and
    sums them under its gates (no scatter-add: on a v5e that walks its rows
    one by one). Where the three leaves come ``Stacked``, the grouped matmuls
    take the whole stack as ``layers x E_loc`` groups of which only this
    layer's have rows: the kernel visits no empty group, and no layer's
    matrices are copied out of the stack for it (1.15 GB a layer at 16
    experts of [6144, 2048])."""
    gmm = pinned_gmm()
    T, D = x.shape
    K = top_e.shape[1]
    stacked = isinstance(wg, Stacked)
    E_loc, _, F = wg.stack.shape[1:] if stacked else wg.shape
    with jax.named_scope("moe_dispatch"):
        ek = top_e.reshape(T * K)
        tok = jnp.arange(T * K, dtype=jnp.int32) // K
        local = (ek >= e0) & (ek < e0 + E_loc)
        key = jnp.where(local, ek - e0, E_loc)  # non-local sorts last
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.bincount(key, length=E_loc + 1).astype(jnp.int32)[:E_loc]
        # non-local rows sort past sum(group_sizes): gmm never computes
        # them (per-shard FLOPs stay ~1/e_sz of the fleet's). Their output
        # AND vjp-cotangent rows are uninitialized, so (a) they gather from
        # a phantom zero row T, and (b) every gmm output is masked so
        # garbage can't ride the elementwise ops into the accumulated
        # gradients or the combine.
        computed = jnp.arange(T * K) < group_sizes.sum()
        s_tok = jnp.where(computed, tok[order], T)
        xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[s_tok]  # [T*K, D] grouped by local expert
        cm = computed[:, None]
        if stacked:  # this layer's groups among every layer's: the others are empty
            n_layers = wg.stack.shape[0]
            group_sizes = jax.lax.dynamic_update_slice(jnp.zeros((n_layers * E_loc,), jnp.int32), group_sizes, (wg.index * E_loc,))
            wg, wu, wd = (w.stack.reshape(n_layers * E_loc, *w.stack.shape[2:]) for w in (wg, wu, wd))
    with jax.named_scope("moe_experts"):
        m = T * K
        t_in, t_out = gmm_tiles(m, D, F, E_loc), gmm_tiles(m, F, D, E_loc)
        if t_in[0] < min(m, 128):
            _warn_small_tile_once((T, K, t_in[0]))
        # outputs in the rows' type (the kernel accumulates in float32): at
        # 32k assignment rows three float32 outputs are 0.75 GB a layer
        kw = dict(preferred_element_type=x.dtype, interpret=interpret)
        g1 = jnp.where(cm, gmm(xs, wg, group_sizes, tiling=t_in, **kw), 0)
        u1 = jnp.where(cm, gmm(xs, wu, group_sizes, tiling=t_in, **kw), 0)
        y = (jax.nn.silu(g1.astype(jnp.float32)) * u1.astype(jnp.float32)).astype(x.dtype)
        yd = jnp.where(cm, gmm(y, wd, group_sizes, tiling=t_out, **kw), 0)
    with jax.named_scope("moe_combine"):
        back = jnp.argsort(order).reshape(T, K)  # where assignment (row, k) sorted to
        g = (gates * local.reshape(T, K)).astype(jnp.float32)
        return jnp.sum(yd[back].astype(jnp.float32) * g[:, :, None], axis=1)


def expert_ffn(x, layer: dict, cfg, *, live=None, e0=0):
    """The sparse-expert FFN on rows x [T, D] for the experts this block
    holds (``layer["we_gate"]`` [E_loc, D, F], global ids from ``e0``).
    Returns (the rows' outputs [T, D] float32, router scores [T, E], chosen
    experts [T, K], load [E] int32: rows that chose each expert).

    The form is chosen by shape, never by model (``takes_dense_form``): up
    to ``DENSE_ROWS`` rows every expert runs on every row, above that rows
    are routed through grouped matmuls. In both forms the gate and up
    projections come out in the rows' type, as every dense MLP's do here
    (silu and the product are float32 on those rounded values; the down
    projection accumulates in float32): before PR 30 the routed form kept
    them float32, which matters to a bfloat16 train step of ``qwen3_moe``
    by one rounding of each ([T*K, F] x 2 x 4 B a layer is what it cost). ``live`` [T] bool marks the rows that hold a request;
    the others count as no load and, where it is free (the dense form),
    take no part. Where the three expert leaves come ``Stacked`` (the caller's
    choice by ``takes_touched_form``, since the caller keeps the stacks
    unsliced), the touched form reads the experts ``load`` says got a row and
    no other; above ``DENSE_ROWS`` rows (a prompt pass) stacked leaves go
    through the grouped matmuls where they lie."""
    with jax.named_scope("moe_router"):
        scores, gates, top_e = route(x, layer["w_router"], cfg, layer.get("router_bias"))
        chose = jax.nn.one_hot(top_e, scores.shape[-1], dtype=jnp.int32).sum(1)  # [T, E]
        if live is not None:
            gates = gates * live[:, None]
            chose = chose * live[:, None]
        load = chose.sum(0)
    wg, wu, wd = layer["we_gate"], layer["we_up"], layer["we_down"]
    if isinstance(wg, Stacked) and takes_dense_form(x.shape[0], wg.stack.shape[1]):
        out = _experts_touched(x, wg, wu, wd, top_e, gates, load, e0)
    elif isinstance(wg, Stacked):
        out = _experts_routed(x, wg, wu, wd, top_e, gates, e0, jax.default_backend() != "tpu")
    elif takes_dense_form(x.shape[0], wg.shape[0]):
        out = _experts_dense(x, wg, wu, wd, top_e, gates, e0)
    else:
        # platform decides compiled-or-interpret, nothing else: on a TPU gmm
        # is always compiled and a kernel the chip refuses is an error
        out = _experts_routed(x, wg, wu, wd, top_e, gates, e0, jax.default_backend() != "tpu")
    return out, scores, top_e, load


def moe_ffn_dropless(h: jax.Array, layer: dict, cfg, live: jax.Array | None = None) -> tuple[jax.Array, ...]:
    """Dropless MoE over the mesh ``expert`` axis.

    With ``live`` [G, L] bool (a serving pass: the rows that hold a request)
    a third value comes back, the rows of ``live`` that chose each expert
    [E] int32, and the others take no part where that is free.

    Inside a shard_map block (token shard x expert shard), ``expert_ffn``
    computes this shard's experts for its rows (``megablox.gmm`` in
    interpret mode off-TPU, so CPU tests exercise the same code); a psum
    over "expert" assembles each token's K expert outputs. Every assignment
    is computed — token conservation is exact
    (tests/test_moe.py::test_dropless_token_conservation).

    Expert weights enter the block gathered over (fsdp, model) — the
    zero-3 per-use gather shard_map's in_specs perform; TP *within* expert
    FFNs is not sharded on this path (EP takes the expert axis; meshes
    that want both should use the capacity path)."""
    from areal_tpu.models.qwen import BATCH_AXES

    G, L, D = h.shape
    E = cfg.num_experts
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
    bias = layer.get("router_bias")
    if live is not None and orig_GL is not None:
        live = live.reshape(G, L)

    def block(h_blk, wr, wg, wu, wd, live_blk=None):
        # h_blk [G_, L_, D]; wg/wu [E_loc, D, F]; wd [E_loc, F, D]
        G_, L_, _ = h_blk.shape
        blk = {"w_router": wr, "we_gate": wg, "we_up": wu, "we_down": wd}
        if bias is not None:
            blk["router_bias"] = bias
        e0 = jax.lax.axis_index("expert") * wg.shape[0] if in_mesh else 0
        out, probs, top_e, load = expert_ffn(
            h_blk.reshape(G_ * L_, D), blk, cfg, e0=e0, live=None if live_blk is None else live_blk.reshape(-1)
        )
        if in_mesh:
            out = jax.lax.psum(out, "expert")
        # switch-style aux from the (replicated-over-expert) global routing
        frac = jax.nn.one_hot(top_e, E, dtype=jnp.float32).reshape(-1, E).mean(0)
        aux = (frac * probs.mean(0)).sum() * E
        if in_mesh:
            aux = jax.lax.pmean(aux, ("data", "fsdp", "seq"))
        out = out.reshape(G_, L_, D).astype(h_blk.dtype)
        if live_blk is None:
            return out, aux
        # every expert shard scores the whole router: the token shards' counts add up
        return out, aux, (jax.lax.psum(load, ("data", "fsdp", "seq")) if in_mesh else load)

    weights = (layer["w_router"], layer["we_gate"], layer["we_up"], layer["we_down"])
    rows = () if live is None else (live,)
    if not in_mesh:
        out, aux, *load = block(h, *weights, *rows)
    else:
        out, aux, *load = shard_map(
            block,
            in_specs=(
                P(BATCH_AXES, "seq", None),
                P(None, None),
                P("expert", None, None),
                P("expert", None, None),
                P("expert", None, None),
                *[P(BATCH_AXES, "seq")] * len(rows),
            ),
            out_specs=(P(BATCH_AXES, "seq", None), P(), *[P()] * len(rows)),
            # gmm's inner pallas_call carries no vma annotations; the variance
            # checker can't see through it — the psum/pmean above implement the
            # replication the out_specs promise
            check_vma=False,
        )(h, *weights, *rows)
    if orig_GL is not None:
        out = out.reshape(*orig_GL, D)
    return (out, aux.astype(jnp.float32), *load)


_SMALL_TILE_WARNED: set = set()


def _warn_small_tile_once(key: tuple) -> None:
    """The routed form with a row count no wide m tile divides: a collapsed
    m tile on a prefill or a train step is a silent perf cliff. (A decode
    step's few rows never come here: they take the dense form.)"""
    if key in _SMALL_TILE_WARNED:
        return
    _SMALL_TILE_WARNED.add(key)
    from areal_tpu.utils import logging as alog

    alog.getLogger("moe").warning(
        "moe gmm m dim T*K=%s*%s has no m tile of 128 or more dividing it; "
        "running with m tile %s — pad the token count to a multiple of 128 "
        "for full throughput" % key
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
