"""Hybrid decoder: layers whose mixer is one of several kinds, in the order
of the published ``layer_types``, each followed by a feed-forward block of
its own kind.

Mixer kinds: ``mamba`` (a Mamba-2 state-space layer), ``attention`` (GQA;
with or without a rotary embedding and a q/k RMSNorm over each head or over
the whole projection, as the configuration says), ``conv`` (a gated short
convolution), ``gdn`` (a gated delta rule: linear attention with a matrix
state a head) and ``mla`` (latent attention: a token leaves ONE row ``[c |
k_r]`` behind, a key/value latent and a rotary key shared by every head).
FFN kinds: ``dense`` (SwiGLU) and ``moe`` (sparse experts, ``models/moe.py``,
with or without an always-active shared block beside them; the stack may hold
a SHARE of the experts the router scores: ``router_experts``,
``expert_first``). A block's two RMSNorms stand on its sublayers' inputs
(``norm_placement`` ``pre``) or on their outputs (``post``); ``norm_kind``
``layer`` makes them LayerNorms with a bias. A decoder-hybrid-decoder
(``phi4flash``: SambaY) adds four mixers: ``s6`` (Mamba-1's selective scan:
a state [state size, channels] with a decay of its own every element),
``swa`` (attention over the last ``sliding_window`` tokens, kept in a RING of
the slot's own: ``inference/paged_kv.py`` RING_LEAVES), ``cross`` (queries of
its own over the pages of the model's ONE ``attention`` layer) and ``gmu`` (a
gate on the last ``s6`` layer's scan output of the same token, which rides
down the stack with the cache), with differential attention in every
attending layer (``_diff_pack_q``: 4 query heads of 2 x head_dim lanes to one
row [k1 | k2], the paged kernels' own grouped-query shape) and a prompt pass
that ends at the shared layer's K and V (``forward_prefill`` ``tail``). A
tenth mixer, ``kda``, is a delta rule whose state decays by a factor of its
OWN every key channel (below), and the ``attention`` mixer may carry an
output gate (``attn_gate``: ``o * sigmoid(W_g u)``, one gate a head and
channel). A block comes in two forms (``block_form``): ``serial``, ``h = x
+ Mixer(norm(x))``, ``out = h + FFN(norm(h))``, two norms a layer; and
``parallel``, ``u = norm(x)``, ``out = x + Mixer(u) + FFN(u)``: ONE norm, the
FFN reads the mixer's INPUT and the two sublayers do not depend on each other
(``_block_ffn``; scope ``block_sum``). The ``swa`` mixer comes in two forms
as well, by the configuration's ``diff_attn``: differential pairs without a
position (``phi4flash``), or PLAIN grouped queries (``_qkv``) whose q and k
take a rotary embedding where ``rope_kinds`` names the mixer (``rotates``; in
(2i, 2i+1) pairs with ``rope_interleave``; scope ``attn_rope``): a key goes
into the slot's ring ALREADY rotated at its own position, so a read takes
the ring as it lies (softmax over q_t . k_s depends on t - s alone). Its
prompt pass attends inside the band ``0 <= t - s < sliding_window``: in XLA
over blocks of ``sliding_window`` queries (``swa_attend``: the CPU path and
the oracle) or, where such a block's [H, window, 2 window] float32 logits do
not fit (17 GB at 128 heads and 4,096), under ONE Pallas launch whose grid IS
the band (``ops/window_prefill_attention.py``, chosen from the shapes:
``swa_prefill_launch``). The always-active block beside the routed experts
may be several shared experts side by side whose outputs are AVERAGED
(``moe_shared_mean_of``). Nine published families are built from these
(``from_hf_dict``): ``xing4_0`` (the ``deepseek_v3`` block through a low-rank
query under a YaRN-scaled rotary key on a residual path of four streams:
below), ``cohere2_moe`` (command-a-plus: the parallel block under
a LayerNorm with a weight and NO bias, ``norm_bias`` False; ``swa`` layers
with the rotary embedding beside ``attention`` layers with none, 3:1, 128
query heads over 8; in EVERY layer sigmoid-routed experts WITHOUT a selection
bias beside four shared ones that are averaged; a tied head times
``logit_scale``; every line an ASSUMPTION where the configuration names a
word and not a formula, listed in the configuration file), ``solar_open2`` (``kda`` beside gated attention without a positional
embedding, 3:1, and in EVERY layer sigmoid-routed experts with a selection
bias beside a shared one: no leading dense layer), ``phi4flash``,
``granitemoehybrid``
without experts (Mamba-2 beside NoPE attention, dense MLPs, residual and
logit multipliers), ``lfm2_moe`` (short convolutions beside rotary attention
with q/k norms; the first ``num_dense_layers`` FFNs dense, the rest 32
experts behind a sigmoid router with a selection bias) and ``olmo_hybrid``
(gated-delta-rule layers beside NoPE attention with whole-projection q/k
norms, post-sublayer norms, dense MLPs) and ``deepseek_v3`` (latent attention
in every layer, a leading dense FFN, then sigmoid-routed experts with a
selection bias beside shared experts).
Serving only (prefill, paged decode); training is ROADMAP Reach A.4.

The module has the entry points the decode engine uses of ``models/qwen.py``
(``models.family_of`` picks one of the two from the model configuration), and
shares with it the RMSNorm, the projection (``_proj``), the rotary embedding,
the embedding lookup, the logits matmul and the scope names.

Params are stacked PER KIND OF LAYER, a layer's kind being its mixer and its
FFN (``stack_name``): ``params["mamba"][name]`` is ``[n_mamba, ...]``,
``params["conv_moe"][name]`` is ``[layers with a conv mixer and experts,
...]``; a run of consecutive layers of one kind is one ``lax.scan`` over its
indices (``_scan_layers``).

The Mamba-2 mixer comes in two forms of one recurrence (per head, state
``S`` in R^{P x N}): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D x_t``. ``ssm_decode_step`` is the recurrence itself, one
token for every slot; ``ssm_chunked_scan`` is the chunked algorithm over a
whole prompt (quadratic inside a chunk of ``mamba_chunk_size`` tokens, the
state carried between chunks). tests/test_hybrid_model.py holds them to each
other. Both compute in float32 whatever the model's dtype: the state is what
thousands of decode steps accumulate into.

The short-conv mixer: ``[B | C | x] = W_in u``, ``g_t = B_t * x_t``,
``c_t = sum_j w_j g_{t-K+1+j}`` per channel (no bias, no activation),
``y_t = W_out (C_t * c_t)``; a slot's state is its last ``K - 1`` values of
``g``. ``conv_prefill`` and ``conv_decode`` are its two forms
(tests/test_lfm2_model.py holds them to each other).

The gated-delta-rule mixer (the ``fla`` library's ``GatedDeltaNet``): q, k,
v each through a projection, a depthwise causal conv of its own and SiLU; q
and k L2-normalised a head; per head a decay ``alpha_t = exp(-exp(A_log)
softplus(W_a x_t + dt_bias))`` and a write strength ``beta_t`` in (0, 1) or
(0, 2); state ``S`` in R^{K x V}: ``S' = alpha_t S_{t-1}``, ``u_t = beta_t
(v_t - S'^T k_t)``, ``S_t = S' + k_t u_t^T``, ``o_t = S_t^T q_t``: a read of
the decayed state BEFORE a rank-one write. ``gdn_decode_step`` is the
recurrence itself (on a TPU the Pallas kernel ``ops/gdn_state_update.py``
over the live slots, in place); ``gdn_chunked_scan`` is the chunked (WY)
algorithm over a whole prompt: inside a chunk of ``GDN_CHUNK`` tokens a
unit lower-triangular system, across chunks the state carried in float32.
tests/test_olmo_hybrid_model.py holds them to each other and to the
token-by-token reference.

The ``kda`` mixer (Kimi Linear's ``KimiDeltaAttention``, arXiv 2510.26692;
``solar_open2``'s linear-attention layer): q, k, v as the gated delta rule's
(a projection, a depthwise causal conv of its own without bias, SiLU; q and k
L2-normalised a head, q scaled by K^-1/2); a log decay a head AND key channel
``a_t[h, :] = -exp(A_log[h]) softplus(W_fb W_fa u + dt_bias)[h, :]`` in R^K
through a low rank; ``beta_t[h] = sigmoid(W_b u)[h]`` (x 2 with
``kda_neg_eigval``); state ``S`` in R^{K x V} a head, float32: ``S' =
diag(exp(a_t)) S_{t-1}``, ``w_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' + k_t
w_t^T``, ``o_t = S_t^T q_t``; ``y = W_o (rmsnorm_head(o_t) * sigmoid(W_gb W_ga
u))``. It is NOT ``gdn`` with other numbers: the decay is a vector over a
head's key channels, so the chunked prompt scan cannot factor it out of ``K
K^T`` as one [C, C] matrix a head. Two forms of one recurrence, as ``gdn``
has: ``kda_decode_step`` is the recurrence itself (on a TPU the Pallas kernel
``ops/kda_state_update.py`` over the live slots, in place, its decay a column
operand a head); ``kda_chunked_scan`` the WY algorithm over a prompt, every
exponent a difference in the direction of time (its docstring says how), the
state carried across chunks, and across the BLOCKS a long prompt goes through
(``kda_prefill``), in float32; on a TPU with heads of whole lane tiles a
block's scan is ONE launch (``ops/kda_prompt_scan.py``, chosen from the
shapes: ``kda_prefill_launch``) and the XLA form its oracle.
tests/test_solar_open2_model.py holds them to each other and to the
token-by-token reference. The gated ``attention``
mixer's prompt pass builds [H, L, L] float32 logits a row where they fit and
attends under ``ops/attention.py flash_fwd_pallas`` where they do not (64
heads past 1,024 tokens), chosen from the shapes (``gqa_prefill_launch``);
the XLA form is the CPU path and the launch's oracle. The layers of a block
(every line an ASSUMPTION the configuration file lists with its reason: the
published ``config.json`` names sizes and switches, not forms): ``u =
rmsnorm(x)``, ``h = x + Mixer(u)``, ``out = h + MoE(rmsnorm(h))``, eps 1e-5,
no bias anywhere; MoE: ``s = sigmoid(W_r x)`` over the router's experts, the
``num_experts_per_tok`` largest of ``s + b``, gates ``s_e / sum(picked s)``,
routed SwiGLU experts beside ONE shared SwiGLU.

The latent-attention mixer comes in two forms of one layer's weights, and the
entry point chooses, never an option: ``forward_prefill`` computes the PLAIN
form (``[k_nope_h | v_h] = W_kvb,h c`` for the prompt's own tokens, every
head's key ``[k_nope_h | k_r]``), ``forward_decode_paged`` the ABSORBED form
over the cached rows (``q^_h = W_UK,h^T q_nope_h``, scores ``q^_h . c_s +
q_rope_h . k_r,s``, ``o_h = W_UV,h sum_s p_s c_s``: the up-projection moved
across both sums, so that a cached token is read as 576 values and not as
32 x 320). tests/test_kanana2_model.py holds them to each other. The prompt
pass is BLOCKED over queries (``mla_prefill_attend``): a block of queries
meets a block of keys at a time under a running softmax, key blocks past the
diagonal are never visited, and nothing of [H, L, L] exists (69 GB at 64
heads and 16k tokens). On a TPU that walk over the key blocks is ONE Pallas
launch a query block (``ops/latent_prefill_attention.py mla_prefill_flash``:
the logits of a head and key block in VMEM, the selection a mask operand),
chosen from the shapes (``prefill_takes_launch``); the XLA loop is the CPU
path and the launch's oracle (tests/test_latent_prefill_attention.py).

Where the layer has a learned index (``index_topk``), a token also leaves ONE
index key behind, in a page pool of its own width on the same page ids
(``kv_pools``), and every query, in both forms and at every context length,
attends to the min(index_topk, t + 1) cached tokens of largest ``I[t, s] =
sum_j w[t, j] relu(q^I[t, j] . k^I[s])`` and to no other. The selection is
EXACT (``select_top``: the k-th largest score found by counting, no sort and
no approximate top-k: S_t is part of the mathematics). A decode step scores
every cached token of every live slot (``index_select``), and reads the
selected rows in the MASKED form: the latent launch fetches every page that
holds tokens and the unselected rows meet a probability of 0
(tests/test_glm5_model.py holds both forms to the reference; PERF.md has what
a gather of the selected rows costs on the chip).

The residual path is data of the configuration too (``residual_form``), beside
``norm_placement`` and ``block_form``. ``sum``: ONE vector a token, ``x +
Sublayer(norm(x))``, every family above. ``mhc`` (``xing4_0``, Xing4.0-29B-A4B:
manifold-constrained hyper-connections, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606; n = ``hc_mult`` = 4, D = hidden): the
carry of both entry points is n STREAMS ``X [rows, n D]``, side by side on the
lanes. ``X_0[j] = E[id]`` for every j (the embedding copied); every sublayer
``F`` (the mixer, then the feed-forward block: two a layer, each with leaves
``Phi``, ``a``, ``b`` of its own): ``x' = vec(X) rsqrt(mean(vec(X)^2) +
rms_norm_eps)`` (no weight), ``m = x' Phi`` in R^{2n + n^2}; ``H_pre =
sigmoid(a_pre m[:n] + b_pre)``, ``H_post = 2 sigmoid(a_post m[n:2n] +
b_post)``, ``Z = clip(a_res mat(m[2n:]) + B_res, hc_res_clamp)``, ``M =
exp(Z)``, then ``hc_sinkhorn_iters`` = 20 times ``M <- M / (rowsum(M) +
hc_eps)``, ``M <- M / (colsum(M) + hc_eps)`` = ``H_res``, doubly stochastic to
the rounds' precision; ``u = sum_j H_pre[j] X[j]``, ``X'[i] = sum_j H_res[i, j]
X[j] + H_post[i] F(norm(u))``; after the last layer ``h = sum_j X[j]``, the
final norm, the head. The coefficients are float32, the streams the served
type (``mhc_coefficients``, ``mhc_pre``, ``mhc_post``, ``_block_in``,
``_block_ffn``, ``_final_norm``; scopes MHC_SCOPES). How the streams are born
and merged, that each sublayer has coefficients of its own, the norm without a
weight, where ``hc_eps`` enters and the coefficients' type are ASSUMPTIONS the
configuration file lists (``_xing4_fields`` refuses every other reading). A
``sum``-form model reaches none of this and traces as it did.

The ``mla`` mixer's rotary embedding may be scaled by YaRN (``rope_scaling``
type ``yarn``, the DeepSeek-V3 family's published form; any other type is
refused): rotary pair i of ``qk_rope_head_dim`` / 2 turns by ``inv_freq_i =
f_i (1 - r_i) + (f_i / factor) r_i``, ``f_i = rope_theta^(-2i / dim)``, ``r_i =
clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(d(beta_fast))``, ``hi =
ceil(d(beta_slow))``, ``d(b) = dim ln(original / (2 pi b)) / (2 ln
rope_theta)`` (``yarn_inv_freq``: lo 10, hi 23 of 32 pairs at Xing4.0's
constants); cos and sin stay unscaled (mscale = mscale_all_dim) and the softmax
scale takes ``(0.1 mscale_all_dim ln(factor) + 1)^2`` (2.0047) in all three
attending forms (``sm_scale``: the prompt launch, the XLA loop, the absorbed
decode read); a key goes into its page rotated by the same table
(``_rope_latent``). The family's multi-token-prediction layer
(``num_nextn_predict_layers``) is NOT implemented: served as a self-draft it
needs verification over latent pages (ROADMAP Reach A.5) and a step that yields
more than one token a row; a configuration that has one is refused.

What a slot's recurrent state is, and who may write it, is in
``inference/paged_kv.py`` (STATE_LEAVES); what a page row is, in ``kv_pools``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from areal_tpu.models import moe, qwen
from areal_tpu.models.qwen import _embed_lookup, _proj, _rms_norm, _rope

MODEL_TYPES = ("granitemoehybrid", "lfm2_moe", "olmo_hybrid", "deepseek_v3", "glm_moe_dsa", "phi4flash", "solar_open2", "cohere2_moe", "xing4_0")
# mixers; ``s6`` is Mamba-1's selective scan, ``swa`` attention over the last ``sliding_window`` tokens, ``cross``
# queries of its own over the pages of the model's ONE ``attention`` layer, ``gmu`` a gated memory unit, ``kda`` a
# delta rule whose state decays by a factor of its own every key channel
KINDS = ("mamba", "attention", "conv", "gdn", "mla", "s6", "swa", "cross", "gmu", "kda")
FFNS = ("dense", "moe")
# scopes this family adds to qwen.SCOPES (docs/observability.md): the
# state-space mixer's, the short-conv mixer's, and models/moe.py's
SCOPES = ("ssm_proj", "ssm_conv", "ssm_state", "state_write")
CONV_SCOPES = ("conv_proj", "conv_mix", "state_write")
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_state", "state_write")
# the delta rule with a decay a key channel, and the output gate of an attention layer that has one
KDA_SCOPES = ("kda_proj", "kda_conv", "kda_state", "state_write")
ATTN_GATE_SCOPE = "attn_gate"
MLA_SCOPES = ("mla_proj", "attn", "kv_write")  # latent attention: its projections beside the shared two
# a low-rank query path, and the learned index that picks the cached tokens a query attends to
DSA_SCOPES = ("mla_q_lora", "dsa_index_proj", "dsa_index_score", "dsa_select")
MOE_SHARED_SCOPE = "moe_shared"  # the always-active block beside the routed experts
# a decoder-hybrid-decoder: the window layers' read, the cross layers' read of the shared pages, what differential
# attention adds behind either read (the subtraction, the norm over a pair's values, lambda), the memory unit
SAMBAY_SCOPES = ("attn_window", "attn_cross", "attn_diff", "gmu")
# a parallel block with rotary window layers: the rotary embedding of the window layers' q and k (both entry points),
# and the block's one residual sum ``x + Attn(u) + MoE(u)``
PARALLEL_SCOPES = ("attn_rope", "block_sum")
# a residual path of several streams (``residual_form`` "mhc"): a sublayer's coefficients from the streams (their
# norm, ``Phi``, the sigmoids, the clamp), the Sinkhorn rounds, the mix a sublayer reads, the mix it writes back
# through, and the streams' sum before the final norm
MHC_SCOPES = ("mhc_coeff", "mhc_sinkhorn", "mhc_pre", "mhc_post", "mhc_merge")
# tokens a chunk of the delta rule's prefill scan: 16 x 2^2, as ``_unit_lower_inverse`` builds its inverse
GDN_CHUNK = 64
# the per-channel-decay delta rule's: the same chunk, in sub-blocks of 16 tokens each referred to its own first
# token (``kda_chunked_scan``); a prompt goes through in blocks of up to this many tokens, the state carried between
KDA_CHUNK, KDA_SUB = 64, 16
_KDA_BLOCK_TOKENS = 1024
# what a decode chunk may ask the forward to count into (not part of the
# cache the engine keeps): rows of live slots each expert got, [expert
# layers, experts], and experts with at least one such row, [expert layers];
# live slots whose delta-rule state a step advanced, [gdn layers] ([kda layers] for ``kda_updates``); cached
# tokens a latent-attention layer's read FETCHED, [mla layers] (the live slots' distinct ones where the launch names a
# block several slots hold once, every live slot's on the gather path and under a selection); where the
# layer has an index, the cached tokens it scored and the tokens the
# mathematics selects of them (min(index_topk, cached) a live slot), [mla layers]
# ... and of a decoder-hybrid-decoder, one number each a chunk: cached tokens of live slots x the layers that read
# the shared pages; tokens a window layer read (at most ``sliding_window`` a live slot and layer); selective-scan
# states advanced (live slots x s6 layers)
# ... and of every model with K and V pages (or latent rows without an index) under the page table, one number each a chunk: the blocks of pages its
# attention launches' work list would hold at one item a (live slot, block), and the items it holds (a block that
# several slots' rows name is fetched once: ops/paged_attention_q8.py shared_decode_schedule)
# ... and of a model whose residual path is several streams, one number a chunk: live slots x sublayers mixed
COUNT_LEAVES = (
    "moe_load", "moe_touched", "moe_streamed", "gdn_updates", "latent_tokens_read",
    "index_tokens_scored", "latent_tokens_selected",
    "shared_kv_tokens_read", "window_tokens_read", "s6_updates",
    "attn_blocks_listed", "attn_blocks_fetched", "kda_updates", "mhc_row_sublayers",
)


def stack_name(kind: str, ffn: str) -> str:
    """The params stack of layers with this mixer and this FFN."""
    return kind if ffn == "dense" else f"{kind}_{ffn}"


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: ``0.1 mscale ln(factor) + 1`` (1 at a factor of 1 or less)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: float, beta_fast: float, beta_slow: float):
    """(the inverse frequency of each of the ``dim // 2`` rotary pairs, lo, hi) under YaRN as the DeepSeek-V3 family
    publishes it: ``f_i = theta^(-2i / dim)``; a pair that turns more than ``beta_fast`` times over the ``original``
    length keeps ``f_i``, one that turns fewer than ``beta_slow`` times takes ``f_i / factor``, and between ``lo =
    floor(d(beta_fast))`` and ``hi = ceil(d(beta_slow))``, ``d(b) = dim ln(original / (2 pi b)) / (2 ln theta)``, a
    linear ramp ``r_i = clip((i - lo) / (hi - lo), 0, 1)`` blends them: ``f_i (1 - r_i) + (f_i / factor) r_i``."""
    def pair_of(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), dim - 1)
    span = (hi - lo) or 0.001  # the published code's guard against a ramp of no width
    out = []
    for i in range(dim // 2):
        f, r = theta ** (-2.0 * i / dim), min(max((i - lo) / span, 0.0), 1.0)
        out.append(f * (1.0 - r) + (f / factor) * r)
    return tuple(out), lo, hi


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]  # the mixer of every layer, of KINDS
    num_heads: int
    num_kv_heads: int
    head_dim: int | None = None  # default hidden_size // num_heads
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None  # softmax scale; None: 1/sqrt(hd)
    logits_scaling: float = 1.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    dtype: str = "bfloat16"
    # the recurrent state's own types: the SSM state accumulates over the
    # whole generation; the conv window holds activations as they are
    ssm_state_dtype: str = "float32"
    conv_state_dtype: str | None = None  # default: dtype
    # K and V pages hold each head zero-padded to a multiple of this many
    # lanes, so that the Pallas paged kernels (128-lane pages) serve a head
    # of 64 exactly; 1 = as published
    kv_lane_pad: int = 128
    # what the serving stack asks of any model configuration
    vision: Any = None
    image_token_id: int = -1
    # the checkpoint format (hf_name_map, to_hf_dict), nothing of the forward
    model_type: str = "granitemoehybrid"
    # attention layers: rotary embedding (None: none) and RMSNorm over each
    # head of q and k before it
    rope_theta: float | None = None
    qk_norm: bool = False
    # the short-conv mixer: taps of its depthwise causal conv
    conv_L_cache: int = 3
    # the FFN of every layer, of FFNS; None: dense everywhere
    ffn_types: tuple[str, ...] | None = None
    fused_gate_up: bool = True  # dense FFN: one [gate | up] matrix, or two
    # sparse experts (models/moe.py reads these off the configuration)
    num_experts: int = 0
    num_experts_per_tok: int = 1
    moe_intermediate_size: int | None = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router_score: str = "softmax"  # or "sigmoid"
    router_bias: bool = False  # a selection bias a layer (gates stay unbiased)
    router_norm_eps: float | None = None
    # where a block's two RMSNorms stand: "pre", on a sublayer's input, or
    # "post", on its output before the residual add (the Olmo family's)
    norm_placement: str = "pre"
    # q/k RMSNorm over each head ("head") or the whole projection ("whole")
    qk_norm_over: str = "head"
    # the gated-delta-rule mixer: heads, a head's key and value size, the
    # taps of its three depthwise convs, beta in (0, 2) rather than (0, 1)
    gdn_n_heads: int = 0
    gdn_k_dim: int = 0
    gdn_v_dim: int = 0
    gdn_d_conv: int = 4
    gdn_neg_eigval: bool = False
    gdn_state_dtype: str = "float32"
    # the latent-attention mixer (``mla``): the rank of the latent a token
    # leaves behind, a head's key without and with rotary embedding, a head's
    # value; the rotary part stored as (even, odd) pairs; lanes a latent row
    # [c | k_r] is stored in (0: the next multiple of 128)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    latent_row_lanes: int = 0
    # ... its query through a normed low-rank bottleneck of this rank (0: one full-rank matrix)
    q_lora_rank: int = 0
    # ... and its learned index (0 heads: none, every query attends to every
    # cached token): ``index_n_heads`` index queries of ``index_head_dim`` read
    # the normed low-rank query, ONE index key a token (a LayerNorm with bias
    # over it, eps ``index_norm_eps``), the first ``qk_rope_head_dim`` values
    # of both rotated; a query attends to the ``index_topk`` cached tokens of
    # largest sum_j w_j relu(q_j . k)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_rope_interleave: bool = True
    index_norm_eps: float = 1e-6
    # an always-active SwiGLU beside the routed experts, of this width (0: none)
    moe_shared_intermediate_size: int = 0
    # the share of an expert layer held here: the router scores
    # ``router_experts`` experts (None: num_experts), of which this stack
    # holds ``num_experts``, global ids from ``expert_first``; what the others
    # would add is left out of the layer's sum
    router_experts: int | None = None
    expert_first: int = 0
    # a block's two norms and the final one: "rms", or "layer" (LayerNorm with weight and bias)
    norm_kind: str = "rms"
    # the selective-scan mixer (``s6``, Mamba-1): channels, the rank its step size is projected through
    # (``mamba_d_state`` and ``mamba_d_conv`` are its state size and conv taps)
    s6_d_inner: int = 0
    s6_dt_rank: int = 0
    # ``swa`` layers attend to the last ``sliding_window`` tokens, the query's own among them
    sliding_window: int = 0
    # differential attention in every attending layer: query heads (2p, 2p+1) are (q1, q2) of differential head p,
    # K heads (2r, 2r+1) are (k1, k2) and V heads (v1 | v2) of pair r, head p reads pair p // 2
    diff_attn: bool = False
    attn_bias: bool = False  # biases on the attending layers' projections
    # an ``attention`` layer's output gate: ``o * sigmoid(W_g u)``, one gate a head and channel
    attn_gate: bool = False
    # the ``kda`` mixer (a delta rule with a decay of its own every key channel): heads, a head's key and value
    # size, the taps of its three depthwise convs, the rank its decay and its output gate are projected through,
    # beta in (0, 2) rather than (0, 1)
    kda_n_heads: int = 0
    kda_k_dim: int = 0
    kda_v_dim: int = 0
    kda_d_conv: int = 4
    kda_rank: int = 0
    kda_neg_eigval: bool = False
    kda_state_dtype: str = "float32"
    # the block: "serial" (``h = x + Mixer(norm(x))``, ``out = h + FFN(norm(h))``: two norms a layer) or "parallel"
    # (``u = norm(x)``, ``out = x + Mixer(u) + FFN(u)``: ONE norm, the FFN reads the mixer's INPUT)
    block_form: str = "serial"
    # a ``norm_kind`` "layer" norm has a bias beside its weight (False: a weight alone)
    norm_bias: bool = True
    # the mixer kinds whose q and k take the rotary embedding ``rope_theta`` (None: every ``attention`` layer, as
    # ever); ``rope_interleave`` then pairs channels (2i, 2i+1) there too
    rope_kinds: tuple[str, ...] | None = None
    # the always-active block is this many shared experts side by side whose outputs are AVERAGED: the
    # down-projection's sum over all of their columns, divided by it (1: summed, one block)
    moe_shared_mean_of: int = 1
    # the residual path: "sum" (ONE vector a token, ``x + Sublayer(norm(x))``) or "mhc" (manifold-constrained
    # hyper-connections: ``hc_mult`` streams a token, mixed before and after every sublayer by per-token coefficients;
    # the stream-to-stream matrix ``exp`` of a logit clamped to ``hc_res_clamp``, made doubly stochastic by
    # ``hc_sinkhorn_iters`` rounds of row then column normalisation with ``hc_eps`` in both denominators)
    residual_form: str = "sum"
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # the ``mla`` mixer's rotary embedding scaled by YaRN (the DeepSeek-V3 family's form): (factor, original length,
    # beta_fast, beta_slow, mscale, mscale_all_dim); None: every pair turns by ``rope_theta`` alone
    rope_yarn: tuple[float, ...] | None = None

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def ffns(self) -> tuple[str, ...]:
        return self.ffn_types or ("dense",) * self.num_layers

    @property
    def num_moe_layers(self) -> int:
        return sum(1 for f in self.ffns if f == "moe")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim_

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim_

    @property
    def sm_scale(self) -> float:
        if self.attention_multiplier is not None:
            return float(self.attention_multiplier)
        if self.count("mla"):
            base = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
            # YaRN's factor on the softmax scale: mscale(factor, mscale_all_dim)^2 (cos and sin stay unscaled)
            return base * yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2 if self.rope_yarn else base
        return self.head_dim_**-0.5

    @property
    def rope_inv_freq(self) -> tuple[float, ...] | None:
        """The ``mla`` mixer's inverse frequency a rotary pair where YaRN scales them (``yarn_inv_freq``); None
        where every pair turns by ``rope_theta ** (-2i / qk_rope_head_dim)``."""
        if self.rope_yarn is None:
            return None
        factor, original, fast, slow = self.rope_yarn[:4]
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta, factor, original, fast, slow)[0]

    @property
    def stream_width(self) -> int:
        """Values a token carries through the layers: ``hc_mult`` streams of ``hidden_size`` side by side on the
        lanes (stream j is lanes j * hidden_size ..), or the one residual vector."""
        return self.hc_mult * self.hidden_size if self.residual_form == "mhc" else self.hidden_size

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def latent_dim(self) -> int:
        """Values a token leaves behind in a latent-attention layer: [c | k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes a latent row is stored in: whole 128-lane tiles."""
        return self.latent_row_lanes or -(-self.latent_dim // 128) * 128

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of the delta-rule mixer's conv window: [q | k | v]."""
        return self.gdn_n_heads * (2 * self.gdn_k_dim + self.gdn_v_dim)

    @property
    def kda_conv_dim(self) -> int:
        """Channels of the ``kda`` mixer's conv window: [q | k | v]."""
        return self.kda_n_heads * (2 * self.kda_k_dim + self.kda_v_dim)

    @property
    def gdn_head_pack(self) -> int:
        """Heads side by side in one tile of the delta-rule state
        (ops/gdn_state_update.py): 2 at a value size of 192."""
        from areal_tpu.ops.gdn_state_update import head_pack

        return head_pack(self.gdn_n_heads, self.gdn_v_dim)

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    # -- what the serving cache holds for this family (paged_kv.py) --------
    @property
    def num_kv_layers(self) -> int:
        return self.count("attention") + self.count("mla")

    @property
    def kv_head_dim(self) -> int:
        if self.count("mla"):
            return self.latent_lanes
        if self.diff_attn:  # a pair's two heads side by side, [k1 | k2] and [v1 | v2]: nothing padded at a head of 64
            return 2 * self.head_dim_
        pad = max(1, self.kv_lane_pad)
        return -(-self.head_dim_ // pad) * pad

    @property
    def kv_pool_heads(self) -> int:
        """Heads a K or V row is stored as: the KV heads, or their pairs."""
        return self.num_kv_heads // 2 if self.diff_attn else self.num_kv_heads

    @property
    def kv_pools(self) -> dict[str, tuple[int, int]]:
        """{page pool: (heads, lanes)} of what a token leaves behind in a
        layer that attends (inference/paged_kv.py): a K and a V row a KV
        head, or ONE latent row [c | k_r | 0] for all heads, whose first
        ``kv_lora_rank`` lanes are its value too."""
        if self.count("mla"):
            # the index's key of a token lies beside its latent row: a second pool of another width on the same pages
            return {"k": (1, self.latent_lanes), **({"idx": (1, self.index_head_dim)} if self.index_topk else {})}
        return {"k": (self.kv_pool_heads, self.kv_head_dim), "v": (self.kv_pool_heads, self.kv_head_dim)}

    def layers_of(self, kind: str) -> tuple[int, ...]:
        """Model indices of the layers whose mixer is ``kind``, in order."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def kv_groups(self) -> dict[str, dict[str, Any]]:
        """Which LAYERS a group of page pools serves and how long they keep a
        token (inference/paged_kv.py): {group: {"pools", "writers" (model
        indices of the layers that leave a row there), "readers" (of those
        that attend over it), "keeps" (tokens a slot holds a layer: None =
        every one, under the page table; a number = the last that many, in a
        ring of the slot's own)}}. ``full`` is what ``kv_pools`` describes."""
        groups: dict[str, dict[str, Any]] = {}
        if self.num_kv_layers:
            own = self.layers_of("attention") + self.layers_of("mla")
            groups["full"] = {
                "pools": tuple(self.kv_pools), "writers": own,
                "readers": tuple(sorted(own + self.layers_of("cross"))), "keeps": None,
            }
        if self.count("swa"):
            from areal_tpu.inference.paged_kv import RING_LEAVES

            swa = self.layers_of("swa")
            groups["window"] = {"pools": RING_LEAVES, "writers": swa, "readers": swa, "keeps": self.sliding_window}
        return groups

    def ring_pages(self, page_size: int) -> int:
        """Pages of a slot's ring in one window layer: ``sliding_window``
        tokens, token t at ring position t % sliding_window."""
        return -(-self.sliding_window // page_size) if self.count("swa") else 0

    def ring_shapes(self, slots: int, page_size: int) -> dict[str, tuple[tuple[int, ...], Any]]:
        """{leaf: (shape, dtype)} of the window layers' rings (paged_kv.py
        RING_LEAVES): [window layers, heads, slots + 1, pages a ring, page,
        lanes]; the block past the last slot takes a padding row's writes."""
        if not self.count("swa"):
            return {}
        from areal_tpu.inference.paged_kv import RING_LEAVES

        shape = (self.count("swa"), self.kv_pool_heads, slots + 1, self.ring_pages(page_size), page_size, self.kv_head_dim)
        return {name: (shape, self.jax_dtype) for name in RING_LEAVES}

    @property
    def has_recurrent_state(self) -> bool:
        return self.count("mamba") + self.count("conv") + self.count("gdn") + self.count("s6") + self.count("kda") > 0

    @property
    def has_slot_tenant(self) -> bool:
        """Whether a slot owns anything beside its pages: a recurrent state,
        or the window layers' rings (what a sibling is handed a COPY of, and
        what a re-prefill after a preemption rebuilds)."""
        return self.has_recurrent_state or self.count("swa") > 0

    def rotates(self, kind: str) -> bool:
        """Whether the q and k of a ``kind`` layer take the rotary embedding."""
        return self.rope_theta is not None and (kind in self.rope_kinds if self.rope_kinds is not None else kind == "attention")

    @property
    def count_shapes(self) -> dict[str, tuple[int, ...]]:
        """{leaf: shape} of every int32 count a decode chunk takes back
        beside its tokens (COUNT_LEAVES, in that order)."""
        out = dict(self.moe_count_shapes)
        if n := self.count("gdn"):
            out["gdn_updates"] = (n,)
        if n := self.count("mla"):
            out["latent_tokens_read"] = (n,)
            if self.index_topk:
                out["index_tokens_scored"] = (n,)
                out["latent_tokens_selected"] = (n,)
        if self.count("cross"):
            out["shared_kv_tokens_read"] = (1,)
        if self.count("swa"):
            out["window_tokens_read"] = (1,)
        if self.count("s6"):
            out["s6_updates"] = (1,)
        if self.count("attention") + self.count("cross") or (self.count("mla") and not self.index_topk):
            out["attn_blocks_listed"] = out["attn_blocks_fetched"] = (1,)
        if n := self.count("kda"):
            out["kda_updates"] = (n,)
        if self.residual_form == "mhc":
            out["mhc_row_sublayers"] = (1,)
        return out

    @property
    def moe_count_shapes(self) -> dict[str, tuple[int, ...]]:
        """{leaf: shape} of the int32 counts a decode chunk takes back beside
        its tokens (COUNT_LEAVES); none for a model without experts. The load
        has the router's width; ``moe_touched`` counts experts held here that
        got a live row, ``moe_streamed`` those whose weights a step read."""
        n = self.num_moe_layers
        return {"moe_load": (n, self.router_width), "moe_touched": (n,), "moe_streamed": (n,)} if n else {}

    def state_shapes(self, slots: int) -> dict[str, tuple[tuple[int, ...], Any]]:
        """{leaf: (shape, dtype)} of the slot-indexed recurrent state. A conv
        window is stored token-major and flat, ``(taps - 1) * channels``
        wide: with the tokens as the minor dimension the TPU would pad every
        channel's 2 or 3 values to a 128-lane row. A model has state-space,
        short-conv, delta-rule (``gdn`` or ``kda``) or selective-scan layers,
        one of the five: ``conv`` is its window (a delta-rule layer's three,
        side by side: [q | k | v]). The delta-rule state ``gdn`` holds
        ``gdn_head_pack`` heads a tile; ``kda`` [layers, slots, H, K, V] a
        head a tile (its value size is whole lane tiles as published)."""
        conv_dtype = jnp.dtype(self.conv_state_dtype or self.dtype)
        if n := self.count("s6"):
            # the state [state size, channels]: channels on the lanes, a decay of its own every element
            return {
                "ssm": ((n, slots, self.mamba_d_state, self.s6_d_inner), jnp.dtype(self.ssm_state_dtype)),
                "conv": ((n, slots, (self.mamba_d_conv - 1) * self.s6_d_inner), conv_dtype),
            }
        if n := self.count("mamba"):
            return {
                "ssm": (
                    (n, slots, self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
                    jnp.dtype(self.ssm_state_dtype),
                ),
                "conv": ((n, slots, (self.mamba_d_conv - 1) * self.conv_dim), conv_dtype),
            }
        if n := self.count("conv"):
            return {"conv": ((n, slots, (self.conv_L_cache - 1) * self.hidden_size), conv_dtype)}
        if n := self.count("gdn"):
            p = self.gdn_head_pack
            return {
                "gdn": (
                    (n, slots, self.gdn_n_heads // p, self.gdn_k_dim, p * self.gdn_v_dim),
                    jnp.dtype(self.gdn_state_dtype),
                ),
                "conv": ((n, slots, (self.gdn_d_conv - 1) * self.gdn_conv_dim), conv_dtype),
            }
        if n := self.count("kda"):
            return {
                "kda": ((n, slots, self.kda_n_heads, self.kda_k_dim, self.kda_v_dim), jnp.dtype(self.kda_state_dtype)),
                "conv": ((n, slots, (self.kda_d_conv - 1) * self.kda_conv_dim), conv_dtype),
            }
        return {}

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "HybridConfig":
        """From a published ``config.json`` of one of MODEL_TYPES. Raises on
        what this module does not implement rather than serving something
        else under the model's name."""
        mt = d.get("model_type")
        if mt not in MODEL_TYPES:
            raise ValueError(f"model_type {mt!r} is not of the hybrid family {MODEL_TYPES}")
        if d.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {d['hidden_act']!r} is not implemented")
        extra = {
            k: d[k]
            for k in (
                "dtype", "ssm_state_dtype", "conv_state_dtype", "gdn_state_dtype", "kda_state_dtype", "kv_lane_pad", "head_dim",
                "latent_row_lanes", "router_experts", "expert_first", "index_norm_eps",
            )
            if k in d
        }
        fields = _FIELDS[mt](d)
        kinds = fields["layer_types"]
        if set(kinds) - set(KINDS) or len(kinds) != d["num_hidden_layers"]:
            raise ValueError(f"layer_types {sorted(set(kinds))} x {len(kinds)} for {d['num_hidden_layers']} layers")
        held, first = fields.get("num_experts", 0), int(extra.get("expert_first", 0))
        width = int(extra.get("router_experts") or held)
        if first < 0 or first + held > width:
            raise ValueError(f"experts {first}..{first + held - 1} are not among the router's {width}")
        if (width != held or first) and not held:
            raise ValueError("router_experts / expert_first describe a share of an expert layer: the model has none")
        return cls(
            model_type=mt,
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            tie_word_embeddings=d.get("tie_word_embeddings", True),
            **fields,
            **extra,
        )

    @classmethod
    def from_hf_path(cls, path: str) -> "HybridConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    def to_hf_dict(self) -> dict[str, Any]:
        """Inverse of ``from_hf_dict`` (a saved checkpoint's config.json)."""
        shared = {
            "model_type": self.model_type,
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "num_hidden_layers": self.num_layers,
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        if self.model_type == "cohere2_moe":
            full = [i for i, t in enumerate(self.layer_types) if t == "attention"]
            return {
                **shared,
                "layer_types": [_COHERE_KINDS_OUT[t] for t in self.layer_types],
                "layer_switch": (full[0] + 1) if full else self.num_layers + 1,
                "order_of_interleaved_layers": "local_attn_first",
                "hidden_act": "silu",
                "use_gated_activation": True,
                "head_dim": self.head_dim_,
                "attention_bias": False,
                "use_qk_norm": False,
                "use_parallel_block": True,
                "use_parallel_embedding": False,
                "use_embedding_sharing": True,
                "layer_norm_eps": self.rms_norm_eps,
                "rms_norm_eps": None,
                "logit_scale": 1.0 / self.logits_scaling,
                "position_embedding_type": "rope_gptj",
                "rope_theta": self.rope_theta,
                "rope_parameters": {"rope_theta": self.rope_theta, "rope_type": "default"},
                "rotary_pct": 1,
                "sliding_window": self.sliding_window,
                "first_k_dense_replace": 0,
                "expert_selection_fn": "sigmoid",
                "num_experts": self.num_experts,
                "num_experts_per_tok": self.num_experts_per_tok,
                "num_shared_experts": self.moe_shared_mean_of,
                "shared_expert_combination_strategy": "average",
                "moe_intermediate_size": self.moe_intermediate_size,
                "norm_topk_prob": self.norm_topk_prob,
                **({"router_experts": self.router_experts, "expert_first": self.expert_first} if self.router_experts else {}),
            }
        if self.model_type == "phi4flash":
            return {
                **shared,
                "hidden_act": "silu",
                "layer_norm_eps": self.rms_norm_eps,
                "mb_per_layer": 2,
                "sliding_window": self.sliding_window,
                "mlp_bias": False,
                "lm_head_bias": False,
                "head_dim": self.head_dim_,
                "mamba_d_state": self.mamba_d_state,
                "mamba_d_conv": self.mamba_d_conv,
                "mamba_expand": self.s6_d_inner // self.hidden_size,
                "mamba_dt_rank": self.s6_dt_rank,
                "attn_bias": self.attn_bias,
            }
        if self.model_type == "solar_open2":
            gqa = list(self.layers_of("attention"))
            return {
                **shared,
                "rms_norm_eps": self.rms_norm_eps,
                "hidden_act": "silu",
                "head_dim": self.head_dim_,
                "use_rope": False,
                "partial_rotary_factor": 1,
                "gqa_layers": gqa,
                "gqa_interval": (gqa[1] - gqa[0] - 1) if len(gqa) > 1 else max(0, self.num_layers - 1),
                "use_gqa_gate": self.attn_gate,
                "linear_attn_config": {
                    "short_conv_kernel_size": self.kda_d_conv,
                    "head_dim": self.kda_k_dim,
                    "num_heads": self.kda_n_heads,
                    "num_kv_heads": None,
                },
                "kda_use_full_proj": False,
                "kda_allow_neg_eigval": self.kda_neg_eigval,
                "first_k_dense_replace": sum(1 for f in self.ffns if f == "dense"),
                "n_routed_experts": self.num_experts,
                "n_shared_experts": self.moe_shared_intermediate_size // max(1, self.moe_intermediate_size or 1),
                "num_experts_per_tok": self.num_experts_per_tok,
                "moe_intermediate_size": self.moe_intermediate_size,
                "norm_topk_prob": self.norm_topk_prob,
                "routed_scaling_factor": self.routed_scaling_factor,
                **({"router_experts": self.router_experts, "expert_first": self.expert_first} if self.router_experts else {}),
            }
        if self.model_type == "olmo_hybrid":
            return {
                **shared,
                "layer_types": [_OLMO_KINDS_OUT[t] for t in self.layer_types],
                "rms_norm_eps": self.rms_norm_eps,
                "hidden_act": "silu",
                "attention_bias": False,
                "rope_parameters": {"rope_theta": self.rope_theta},
                "linear_num_key_heads": self.gdn_n_heads,
                "linear_num_value_heads": self.gdn_n_heads,
                "linear_key_head_dim": self.gdn_k_dim,
                "linear_value_head_dim": self.gdn_v_dim,
                "linear_conv_kernel_dim": self.gdn_d_conv,
                "linear_allow_neg_eigval": self.gdn_neg_eigval,
            }
        if self.model_type in ("deepseek_v3", "glm_moe_dsa", "xing4_0"):
            n_dense = sum(1 for f in self.ffns if f == "dense")
            yarn = None
            if self.rope_yarn:
                yarn_keys = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")
                yarn = {"type": "yarn", **dict(zip(yarn_keys, self.rope_yarn)), "original_max_position_embeddings": int(self.rope_yarn[1])}
            streams = {
                "hc_mult": self.hc_mult,
                "hc_sinkhorn_iters": self.hc_sinkhorn_iters,
                "hc_eps": self.hc_eps,
                "mhc_h_res_clamp_min": self.hc_res_clamp[0],
                "mhc_h_res_clamp_max": self.hc_res_clamp[1],
            }
            index = {
                "index_n_heads": self.index_n_heads,
                "index_head_dim": self.index_head_dim,
                "index_topk": self.index_topk,
                "indexer_rope_interleave": self.index_rope_interleave,
                "index_norm_eps": self.index_norm_eps,
            }
            return {
                **shared,
                "rms_norm_eps": self.rms_norm_eps,
                "hidden_act": "silu",
                "attention_bias": False,
                "rope_theta": self.rope_theta,
                "rope_scaling": yarn,
                "rope_interleave": self.rope_interleave,
                **(streams if self.residual_form == "mhc" else {}),
                "q_lora_rank": self.q_lora_rank or None,
                **(index if self.index_topk else {}),
                "kv_lora_rank": self.kv_lora_rank,
                "qk_nope_head_dim": self.qk_nope_head_dim,
                "qk_rope_head_dim": self.qk_rope_head_dim,
                "v_head_dim": self.v_head_dim,
                "first_k_dense_replace": n_dense,
                "moe_layer_freq": 1,
                "n_routed_experts": self.num_experts,
                "n_shared_experts": self.moe_shared_intermediate_size // max(1, self.moe_intermediate_size or 1),
                "num_experts_per_tok": self.num_experts_per_tok,
                "moe_intermediate_size": self.moe_intermediate_size,
                "norm_topk_prob": self.norm_topk_prob,
                "routed_scaling_factor": self.routed_scaling_factor,
                "scoring_func": "sigmoid",
                "topk_method": "noaux_tc",
                "n_group": 1,
                "topk_group": 1,
                **({"router_experts": self.router_experts, "expert_first": self.expert_first} if self.router_experts else {}),
            }
        if self.model_type == "lfm2_moe":
            return {
                **shared,
                "layer_types": ["full_attention" if t == "attention" else t for t in self.layer_types],
                "norm_eps": self.rms_norm_eps,
                "rope_theta": self.rope_theta,
                "conv_L_cache": self.conv_L_cache,
                "conv_bias": False,
                "num_dense_layers": sum(1 for f in self.ffns if f == "dense"),
                "num_experts": self.num_experts,
                "num_experts_per_tok": self.num_experts_per_tok,
                "moe_intermediate_size": self.moe_intermediate_size,
                "norm_topk_prob": self.norm_topk_prob,
                "routed_scaling_factor": self.routed_scaling_factor,
                "use_expert_bias": self.router_bias,
            }
        return {
            **shared,
            "shared_intermediate_size": self.intermediate_size,
            "layer_types": list(self.layer_types),
            "rms_norm_eps": self.rms_norm_eps,
            "embedding_multiplier": self.embedding_multiplier,
            "residual_multiplier": self.residual_multiplier,
            "attention_multiplier": self.sm_scale,
            "logits_scaling": self.logits_scaling,
            "mamba_n_heads": self.mamba_n_heads,
            "mamba_d_head": self.mamba_d_head,
            "mamba_d_state": self.mamba_d_state,
            "mamba_n_groups": self.mamba_n_groups,
            "mamba_d_conv": self.mamba_d_conv,
            "mamba_chunk_size": self.mamba_chunk_size,
            "mamba_expand": self.d_inner // self.hidden_size,
            "mamba_conv_bias": True,
            "mamba_proj_bias": False,
            "attention_bias": False,
            "position_embedding_type": "nope",
            "num_local_experts": 0,
            "hidden_act": "silu",
        }


def _granite_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``granitemoehybrid`` without experts: Mamba-2 beside NoPE attention."""
    if d.get("num_local_experts", 0):
        raise ValueError("granitemoehybrid with routed experts is not implemented (dense shared MLP only)")
    if d.get("position_embedding_type", "nope") != "nope":
        raise ValueError("granitemoehybrid with rotary attention layers is not implemented (NoPE only)")
    if d.get("attention_bias") or d.get("mamba_proj_bias"):
        raise ValueError("projection biases are not implemented for the hybrid family")
    if not d.get("mamba_conv_bias", True):
        raise ValueError("a conv without bias is not implemented for the state-space mixer")
    if d["mamba_n_heads"] * d["mamba_d_head"] != d.get("mamba_expand", 2) * d["hidden_size"]:
        raise ValueError("mamba_n_heads * mamba_d_head must equal mamba_expand * hidden_size")
    if "conv" in d["layer_types"]:
        raise ValueError("granitemoehybrid has no short-conv layers")
    return dict(
        intermediate_size=d.get("shared_intermediate_size", d["intermediate_size"]),
        layer_types=tuple(d["layer_types"]),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        embedding_multiplier=d.get("embedding_multiplier", 1.0),
        residual_multiplier=d.get("residual_multiplier", 1.0),
        attention_multiplier=d.get("attention_multiplier"),
        logits_scaling=d.get("logits_scaling", 1.0),
        mamba_n_heads=d["mamba_n_heads"],
        mamba_d_head=d["mamba_d_head"],
        mamba_d_state=d["mamba_d_state"],
        mamba_n_groups=d.get("mamba_n_groups", 1),
        mamba_d_conv=d.get("mamba_d_conv", 4),
        mamba_chunk_size=d.get("mamba_chunk_size", 256),
    )


def _lfm2_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``lfm2_moe``: short convolutions beside rotary attention with q/k
    norms; ``num_dense_layers`` dense FFNs, then experts behind a sigmoid
    router whose selection (not its gates) takes a bias a layer. The score
    function, the biased selection and the 1e-6 of the normalisation are the
    family's published implementation (``Lfm2MoeSparseMoeBlock``): its
    ``config.json`` has no key for them."""
    if d.get("conv_bias"):
        raise ValueError("lfm2_moe with a conv bias is not implemented")
    if d.get("rope_scaling") or (d.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        raise ValueError("lfm2_moe with a scaled rotary embedding is not implemented")
    if d.get("num_shared_experts", 0):
        raise ValueError("lfm2_moe with shared experts is not implemented")
    kinds = tuple("attention" if t == "full_attention" else t for t in d["layer_types"])
    if set(kinds) - {"attention", "conv"}:
        raise ValueError(f"lfm2_moe layer_types {sorted(set(d['layer_types']))}: only conv and full_attention")
    n_dense = int(d.get("num_dense_layers", 0))
    n = len(kinds)
    experts = int(d.get("num_experts", 0)) if n_dense < n else 0
    if n_dense < n and experts < 1:
        raise ValueError("lfm2_moe layers past num_dense_layers need num_experts")
    theta = d.get("rope_theta") or (d.get("rope_parameters") or {}).get("rope_theta", 1e6)
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=kinds,
        rms_norm_eps=d.get("norm_eps", 1e-5),
        rope_theta=float(theta),
        qk_norm=True,
        conv_L_cache=int(d.get("conv_L_cache", 3)),
        ffn_types=tuple("dense" if i < n_dense else "moe" for i in range(n)),
        fused_gate_up=False,
        num_experts=experts,
        num_experts_per_tok=int(d.get("num_experts_per_tok", 1)),
        moe_intermediate_size=d.get("moe_intermediate_size"),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        router_score="sigmoid",
        router_bias=bool(d.get("use_expert_bias", True)),
        router_norm_eps=1e-6,
    )


_OLMO_KINDS = {"linear_attention": "gdn", "full_attention": "attention"}
_OLMO_KINDS_OUT = {v: k for k, v in _OLMO_KINDS.items()}


def _olmo_hybrid_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``olmo_hybrid``: gated-delta-rule layers beside full attention without
    a rotary embedding, the Olmo family's block (an RMSNorm on each
    sublayer's OUTPUT, q and k normed over the whole projection). The layer
    form is the ``fla`` library's ``GatedDeltaNet`` (separate q/k/v
    projections and convs without bias, SiLU, L2-normed q and k, a gate
    and a gated norm on the output): its ``config.json`` names the sizes,
    not the form."""
    if d.get("attention_bias"):
        raise ValueError("projection biases are not implemented for the hybrid family")
    rope = d.get("rope_parameters") or {}
    if d.get("rope_theta") is not None or rope.get("rope_theta") is not None or d.get("rope_scaling"):
        raise ValueError("olmo_hybrid with a rotary embedding is not implemented (its published rope_theta is null)")
    if d.get("sliding_window"):
        raise ValueError("olmo_hybrid with sliding-window attention is not implemented")
    if set(d["layer_types"]) - set(_OLMO_KINDS):
        raise ValueError(f"olmo_hybrid layer_types {sorted(set(d['layer_types']))}: only {sorted(_OLMO_KINDS)}")
    if d.get("norm_placement", "post") != "post" or d.get("qk_norm_over", "whole") != "whole":
        raise ValueError("olmo_hybrid is implemented with post-sublayer norms and whole-projection q/k norms only")
    heads = int(d["linear_num_value_heads"])
    if int(d.get("linear_num_key_heads", heads)) != heads:
        raise ValueError("a delta-rule layer with fewer key heads than value heads is not implemented")
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=tuple(_OLMO_KINDS[t] for t in d["layer_types"]),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=None,
        qk_norm=True,
        qk_norm_over="whole",
        norm_placement="post",
        fused_gate_up=False,
        gdn_n_heads=heads,
        gdn_k_dim=int(d["linear_key_head_dim"]),
        gdn_v_dim=int(d["linear_value_head_dim"]),
        gdn_d_conv=int(d.get("linear_conv_kernel_dim", 4)),
        gdn_neg_eigval=bool(d.get("linear_allow_neg_eigval", False)),
    )


def _deepseek_v3_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``deepseek_v3`` and ``glm_moe_dsa``: latent attention in every layer (a
    full-rank query or, with ``q_lora_rank``, one through a normed low-rank
    bottleneck; a key/value latent of ``kv_lora_rank`` beside ONE rotary key
    of ``qk_rope_head_dim`` for all heads), the first
    ``first_k_dense_replace`` FFNs dense, the rest ``n_routed_experts``
    experts behind a sigmoid router whose selection (not its gates) takes
    ``e_score_correction_bias``, beside ``n_shared_experts`` always-active
    ones fused into one SwiGLU. The 1e-20 of the gates' normalisation is the
    family's published implementation (``DeepseekV3TopkRouter``).
    ``glm_moe_dsa`` adds DeepSeek-V3.2's learned index (``index_n_heads``,
    ``index_head_dim``, ``index_topk``; it reads the low-rank query, so it
    needs one). What this module does not implement is refused, never
    ignored."""
    rope = d.get("rope_parameters") or {}
    if int(d.get("n_group") or 1) != 1 or int(d.get("topk_group") or 1) != 1:
        raise ValueError(f"{d['model_type']} with group-limited routing (n_group / topk_group other than 1) is not implemented")
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"{d['model_type']} with a scaled rotary embedding under rope_parameters (rope_type) is not implemented")
    yarn = None
    if (scaling := d.get("rope_scaling")) is not None:
        # YaRN as the family publishes it (``yarn_inv_freq``; its factor on the softmax scale, ``HybridConfig.sm_scale``)
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"{d['model_type']} with rope_scaling {scaling!r} is not implemented: only type 'yarn'")
        if d.get("yarn_form", "deepseek_v3") != "deepseek_v3":
            raise ValueError(f"yarn_form {d['yarn_form']!r} is not implemented: only the DeepSeek-V3 family's published form")
        yarn = tuple(
            float(scaling.get(k, default))
            for k, default in (
                ("factor", 1.0), ("original_max_position_embeddings", d.get("max_position_embeddings", 4096)),
                ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 0.0),
            )
        )
        if yarn_mscale(yarn[0], yarn[4]) != yarn_mscale(yarn[0], yarn[5]):
            raise ValueError("rope_scaling with mscale other than mscale_all_dim (scaled cos and sin) is not implemented")
    if d.get("attention_bias"):
        raise ValueError("projection biases are not implemented for the hybrid family")
    if d.get("scoring_func", "sigmoid") != "sigmoid" or d.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"{d['model_type']} router {d.get('scoring_func')!r} / {d.get('topk_method')!r}: only sigmoid / noaux_tc")
    if int(d.get("moe_layer_freq", 1)) != 1:
        raise ValueError(f"{d['model_type']} with moe_layer_freq other than 1 is not implemented")
    if int(d.get("num_nextn_predict_layers") or 0):
        raise ValueError(
            f"{d['model_type']} with num_nextn_predict_layers > 0 is not implemented: a multi-token-prediction "
            "layer served as a draft needs verification over latent pages (ROADMAP Reach A.5); set it to 0 to serve without"
        )
    index = {k: int(d.get(k) or 0) for k in ("index_n_heads", "index_head_dim", "index_topk")}
    if any(index.values()):
        if not all(index.values()) or not d.get("q_lora_rank"):
            raise ValueError(f"an index needs index_n_heads, index_head_dim, index_topk and a low-rank query (q_lora_rank): {index}")
        if index["index_head_dim"] < int(d["qk_rope_head_dim"]) or index["index_head_dim"] % 128:
            raise ValueError(f"index_head_dim {index['index_head_dim']}: whole 128-lane tiles that hold the rotary part are implemented")
    n = int(d["num_hidden_layers"])
    n_dense = min(n, int(d.get("first_k_dense_replace", 0)))
    experts = int(d.get("n_routed_experts") or 0) if n_dense < n else 0
    if n_dense < n and experts < 1:
        raise ValueError(f"{d['model_type']} layers past first_k_dense_replace need n_routed_experts")
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=("mla",) * n,
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=float(d.get("rope_theta") or rope.get("rope_theta") or 10000.0),
        rope_yarn=yarn,
        rope_interleave=bool(d.get("rope_interleave", True)),
        q_lora_rank=int(d.get("q_lora_rank") or 0),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        **index,
        index_rope_interleave=bool(d.get("indexer_rope_interleave", True)),
        ffn_types=tuple("dense" if i < n_dense else "moe" for i in range(n)),
        fused_gate_up=False,
        num_experts=experts,
        num_experts_per_tok=int(d.get("num_experts_per_tok", 1)),
        moe_intermediate_size=d.get("moe_intermediate_size"),
        moe_shared_intermediate_size=int(d.get("n_shared_experts") or 0) * int(d.get("moe_intermediate_size") or 0),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        router_score="sigmoid",
        router_bias=True,
        router_norm_eps=1e-20,
    )


def _xing4_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``xing4_0``: the DeepSeek-V3 block (``_deepseek_v3_fields``: latent attention through a low-rank query, a
    YaRN-scaled rotary key, leading dense FFNs, then sigmoid-routed experts beside a shared one) on a residual path
    of ``hc_mult`` STREAMS (manifold-constrained hyper-connections, arXiv:2512.24880; the module's docstring has the
    equations). The published ``config.json`` names the sizes (``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min/max``); how the streams are born and merged, where ``hc_eps`` enters, the stream norm's
    weight and the coefficients' type are ASSUMPTIONS a configuration file lists: each is read here under its key
    and any reading but the implemented one is refused, never ignored. No index, no multi-token-prediction layer."""
    for key, want in (
        ("stream_init", "embedding_copied"), ("stream_merge", "sum"), ("hc_per_sublayer", True), ("hc_norm_weight", "none"),
        ("hc_eps_in", "both_denominators"), ("hc_coeff_dtype", "float32"),
    ):
        if d.get(key, want) != want:
            raise ValueError(f"xing4_0 with {key} {d[key]!r} is not implemented (only {want!r})")
    if any(d.get(k) for k in ("index_n_heads", "index_head_dim", "index_topk")):
        raise ValueError("xing4_0 with a learned index is not implemented")
    n, rounds = int(d.get("hc_mult", 1)), int(d.get("hc_sinkhorn_iters", 0))
    if n < 1 or rounds < 1:
        raise ValueError(f"xing4_0 needs hc_mult >= 1 and hc_sinkhorn_iters >= 1: {n}, {rounds}")
    return dict(
        _deepseek_v3_fields(d),
        residual_form="mhc",
        hc_mult=n,
        hc_sinkhorn_iters=rounds,
        hc_eps=float(d.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(d.get("mhc_h_res_clamp_min", -30.0)), float(d.get("mhc_h_res_clamp_max", 30.0))),
    )


def _phi4flash_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``phi4flash`` (SambaY, arXiv:2507.06607: a decoder-hybrid-decoder): a
    self-decoder of ``num_hidden_layers // 2 + 2`` layers, Mamba-1 (``s6``) at
    the even indices and attention at the odd ones, over the last
    ``sliding_window`` tokens but for the LAST, which attends to every token
    and whose keys and values are the one cache the cross-decoder reads; then
    a cross-decoder that alternates gated memory units (``gmu``: a gate on
    the last ``s6`` layer's scan output of the same token) and ``cross``
    layers (queries of their own over that one cache). Differential
    attention in every attending layer, LayerNorm with a bias, no positional
    embedding. The published ``config.json`` gives ``mb_per_layer``,
    ``sliding_window`` and the widths; the Mamba sizes (``mamba_d_state``,
    ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``), ``head_dim`` and
    the projections' biases are the family's released code's, read here
    where a configuration names them and refused where it names another form."""
    n = int(d["num_hidden_layers"])
    if int(d.get("mb_per_layer", 2)) != 2 or n % 4 or n < 8:
        raise ValueError(f"phi4flash with mb_per_layer {d.get('mb_per_layer')} / {n} layers: Mamba at every second layer of a depth divisible by 4 is implemented")
    if not d.get("sliding_window"):
        raise ValueError("phi4flash needs sliding_window: its self-decoder's attention layers keep a window")
    if d.get("mlp_bias") or d.get("lm_head_bias"):
        raise ValueError("phi4flash with mlp_bias / lm_head_bias is not implemented")
    if d.get("mamba_proj_bias") or not d.get("mamba_conv_bias", True):
        raise ValueError("phi4flash: a conv with bias and projections without are implemented for the selective-scan mixer")
    if d["num_attention_heads"] % 4 or d.get("num_key_value_heads", d["num_attention_heads"]) * 2 != d["num_attention_heads"]:
        raise ValueError("differential attention is implemented for query heads in pairs, two pairs reading one pair of K/V heads")
    half = n // 2
    kinds = tuple(
        ("s6" if i % 2 == 0 else "attention" if i == half + 1 else "swa") if i <= half + 1 else ("gmu" if i % 2 == 0 else "cross")
        for i in range(n)
    )
    inner = int(d.get("mamba_expand", 2)) * int(d["hidden_size"])
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=kinds,
        rms_norm_eps=d.get("layer_norm_eps", 1e-5),
        norm_kind="layer",
        rope_theta=None,
        sliding_window=int(d["sliding_window"]),
        diff_attn=True,
        attn_bias=bool(d.get("attn_bias", True)),
        s6_d_inner=inner,
        s6_dt_rank=int(d.get("mamba_dt_rank") or -(-int(d["hidden_size"]) // 16)),
        mamba_d_state=int(d.get("mamba_d_state", 16)),
        mamba_d_conv=int(d.get("mamba_d_conv", 4)),
    )


def _solar_open2_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``solar_open2``: gated softmax attention without a positional embedding
    at the layers ``gqa_layers`` names (grouped queries, ``o * sigmoid(W_g
    u)`` before the output projection), a delta rule with a decay of its own
    every key channel (``kda``) everywhere else, and in EVERY layer past
    ``first_k_dense_replace`` ``n_routed_experts`` experts behind a sigmoid
    router with a selection bias beside ``n_shared_experts`` always-active
    ones. The published ``config.json`` names sizes and switches
    (``linear_attn_config``, ``use_gqa_gate``, ``kda_use_full_proj``,
    ``kda_allow_neg_eigval``), not forms: the delta-rule layer is Kimi
    Linear's ``KimiDeltaAttention`` (arXiv 2510.26692), the gate arXiv
    2505.06708's elementwise one, the router the DeepSeek-V3 lineage's
    (sigmoid scores, ``e_score_correction_bias``, gates normalised over the
    chosen): ASSUMPTIONS a configuration file lists. What this module does
    not implement is refused, never ignored."""
    if d.get("use_rope"):
        raise ValueError("solar_open2 with use_rope is not implemented (its attention layers carry no position)")
    if d.get("kda_use_full_proj"):
        raise ValueError("solar_open2 with kda_use_full_proj is not implemented (the decay's low-rank projection is)")
    if not d.get("use_gqa_gate", True):
        raise ValueError("solar_open2 without use_gqa_gate is not implemented")
    if d.get("attention_bias") or int(d.get("n_group") or 1) != 1 or int(d.get("topk_group") or 1) != 1:
        raise ValueError("solar_open2 with projection biases or group-limited routing is not implemented")
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"solar_open2 router {d.get('scoring_func')!r}: only sigmoid")
    lin = d["linear_attn_config"]
    heads, hd = int(lin["num_heads"]), int(lin["head_dim"])
    if lin.get("num_kv_heads") not in (None, heads):
        raise ValueError("a kda layer with fewer key/value heads than query heads is not implemented")
    n = int(d["num_hidden_layers"])
    gqa = {int(i) for i in d["gqa_layers"]}
    if not gqa <= set(range(n)):
        raise ValueError(f"gqa_layers {sorted(gqa)} are not among the model's {n} layers")
    n_dense = min(n, int(d.get("first_k_dense_replace", 0)))
    experts = int(d.get("n_routed_experts") or 0) if n_dense < n else 0
    if n_dense < n and experts < 1:
        raise ValueError("solar_open2 layers past first_k_dense_replace need n_routed_experts")
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=tuple("attention" if i in gqa else "kda" for i in range(n)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        rope_theta=None,
        attn_gate=True,
        kda_n_heads=heads,
        kda_k_dim=hd,
        kda_v_dim=hd,
        kda_d_conv=int(lin.get("short_conv_kernel_size", 4)),
        kda_rank=hd,  # Kimi Linear projects the decay and the output gate through the head size
        kda_neg_eigval=bool(d.get("kda_allow_neg_eigval", False)),
        ffn_types=tuple("dense" if i < n_dense else "moe" for i in range(n)),
        fused_gate_up=False,
        num_experts=experts,
        num_experts_per_tok=int(d.get("num_experts_per_tok", 1)),
        moe_intermediate_size=d.get("moe_intermediate_size"),
        moe_shared_intermediate_size=int(d.get("n_shared_experts") or 0) * int(d.get("moe_intermediate_size") or 0),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        router_score="sigmoid",
        router_bias=True,
        router_norm_eps=1e-20,
    )


_COHERE_KINDS = {"sliding_attention": "swa", "full_attention": "attention"}
_COHERE_KINDS_OUT = {v: k for k, v in _COHERE_KINDS.items()}


def _cohere2_moe_fields(d: dict[str, Any]) -> dict[str, Any]:
    """``cohere2_moe`` (Command A+): a PARALLEL block under ONE LayerNorm
    without bias (``use_parallel_block``: ``out = x + Attn(u) + MoE(u)``), the
    layers ``layer_types`` calls ``sliding_attention`` attending to the last
    ``sliding_window`` tokens with a rotary embedding in (2i, 2i+1) pairs
    (``rope_gptj``), the ``full_attention`` layers to every token with NO
    positional embedding, grouped queries without q/k norm in both; in every
    layer (``first_k_dense_replace`` 0) ``num_experts`` experts of
    ``intermediate_size`` behind a sigmoid router WITHOUT a selection bias,
    beside ``num_shared_experts`` always-active ones whose outputs are
    averaged (``shared_expert_combination_strategy``: the mean over the shared
    experts, added to the routed sum: an ASSUMPTION a configuration file
    lists). ``logit_scale`` multiplies the tied head's logits. What this
    module does not implement is refused, never ignored; the keys
    ``prefix_dense_*`` are read by no layer at ``first_k_dense_replace`` 0."""
    rope = d.get("rope_parameters") or {}
    for key, want in (
        ("attention_bias", False), ("use_qk_norm", False), ("use_parallel_block", True), ("use_parallel_embedding", False),
        ("use_gated_activation", True), ("expert_selection_fn", "sigmoid"), ("position_embedding_type", "rope_gptj"),
        ("rotary_pct", 1), ("shared_expert_combination_strategy", "average"), ("tie_word_embeddings", True),
    ):
        if d.get(key, want) != want:
            raise ValueError(f"cohere2_moe with {key} {d[key]!r} is not implemented (only {want!r})")
    if int(d.get("first_k_dense_replace") or 0):
        raise ValueError("cohere2_moe with leading dense layers (first_k_dense_replace > 0) is not implemented")
    if d.get("rope_scaling") is not None or rope.get("rope_type", "default") != "default":
        raise ValueError("cohere2_moe with a scaled rotary embedding is not implemented")
    if set(d["layer_types"]) - set(_COHERE_KINDS):
        raise ValueError(f"cohere2_moe layer_types {sorted(set(d['layer_types']))}: only {sorted(_COHERE_KINDS)}")
    kinds = tuple(_COHERE_KINDS[t] for t in d["layer_types"])
    if "swa" in kinds and not d.get("sliding_window"):
        raise ValueError("cohere2_moe with sliding_attention layers needs sliding_window")
    experts, shared = int(d.get("num_experts") or 0), int(d.get("num_shared_experts") or 0)
    if experts < 1:
        raise ValueError("cohere2_moe needs num_experts: every layer is an expert layer")
    width = int(d.get("moe_intermediate_size") or d["intermediate_size"])  # no key of its own: the catalog's reading
    return dict(
        intermediate_size=d["intermediate_size"],
        layer_types=kinds,
        rms_norm_eps=float(d.get("layer_norm_eps", 1e-5)),
        norm_kind="layer",
        norm_bias=False,
        block_form="parallel",
        rope_theta=float(d.get("rope_theta") or rope.get("rope_theta") or 10000.0),
        rope_kinds=("swa",),
        rope_interleave=True,
        sliding_window=int(d.get("sliding_window") or 0),
        logits_scaling=1.0 / float(d.get("logit_scale", 1.0)),
        ffn_types=("moe",) * len(kinds),
        fused_gate_up=False,
        num_experts=experts,
        num_experts_per_tok=int(d.get("num_experts_per_tok", 1)),
        moe_intermediate_size=width,
        moe_shared_intermediate_size=shared * width,
        moe_shared_mean_of=max(1, shared),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        router_score="sigmoid",
        router_bias=False,
    )


_FIELDS = {
    "cohere2_moe": _cohere2_moe_fields,
    "solar_open2": _solar_open2_fields,
    "phi4flash": _phi4flash_fields,
    "granitemoehybrid": _granite_fields,
    "lfm2_moe": _lfm2_fields,
    "olmo_hybrid": _olmo_hybrid_fields,
    "deepseek_v3": _deepseek_v3_fields,
    "glm_moe_dsa": _deepseek_v3_fields,
    "xing4_0": _xing4_fields,
}


def prefill_row_bytes(cfg: HybridConfig, bucket: int) -> int:
    """Bytes of the widest activations ONE row of a prefill program of
    ``bucket`` tokens holds through the layers: the residual stream, or,
    where the model has selective-scan layers, the scan's three float32
    inputs and outputs a channel (c, d, y [bucket, channels]: 61 KB a token
    at 5,120 channels against the stream's 5 KB). A long prompt of such a
    model then goes through alone: ONE program a bucket, and the scan's
    temporaries once."""
    stream = qwen.prefill_row_bytes(cfg, bucket)
    if cfg.residual_form == "mhc":
        # ``hc_mult`` streams a token, and a sublayer's post-mix writes the new ones beside the old (a 16k prompt's
        # are 470 MB where one stream is 117 MB): a long prompt goes through alone
        return 2 * cfg.hc_mult * stream
    if cfg.block_form == "parallel":
        # ONE norm's output feeds both sublayers, so it lives through the layer beside the stream; the queries, their
        # rotated copy and the attention's output are each [bucket, heads * head_dim] (0.5 GB at 16k tokens of 128
        # heads), and a block of ``ffn_block_rows`` rows holds the shared experts' gate, up and product
        size = jnp.dtype(cfg.jax_dtype).itemsize
        rows = ffn_block_rows(cfg, "moe", bucket)
        return 2 * stream + 3 * bucket * cfg.q_dim * size + 3 * rows * cfg.moe_shared_intermediate_size * size
    if cfg.count("kda"):
        # a block of the prompt (``kda_prefill``): q, k, v, the log decay and its running sum in float32 [block,
        # H, K], four masked copies of the keys a chunk's sub-blocks meet, and the chunk matrices beside them
        block = min(bucket, _KDA_BLOCK_TOKENS)
        return max(stream, 12 * block * cfg.kda_n_heads * cfg.kda_k_dim * 4)
    return max(stream, 3 * bucket * cfg.s6_d_inner * 4) if cfg.count("s6") else stream


def serving_config(cfg: HybridConfig, dtype: str) -> HybridConfig:
    """``cfg`` as a decode engine serves it."""
    return dataclasses.replace(cfg, dtype=dtype)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_kinds(cfg: HybridConfig) -> list[tuple[str, str]]:
    """(mixer, FFN) of every layer, in model order."""
    return list(zip(cfg.layer_types, cfg.ffns))


def _layer_shapes(cfg: HybridConfig) -> dict[str, dict[str, tuple[int, ...]]]:
    """{stack: {leaf: shape of one layer}} for the kinds of layer the model
    has, in the order they first appear."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, C = cfg.mamba_n_heads, cfg.conv_dim
    # a parallel block has ONE norm: what both of its sublayers read
    norms = {"input_norm": (D,)} if cfg.block_form == "parallel" else {"input_norm": (D,), "post_norm": (D,)}
    if cfg.norm_kind == "layer" and cfg.norm_bias:
        norms.update({f"{n}_bias": (D,) for n in tuple(norms)})
    if cfg.residual_form == "mhc":
        # a sublayer's stream coefficients (``mhc_coefficients``): ``Phi`` over the flattened streams, the three gains
        # (pre, post, res) and the biases [pre (n) | post (n) | res (n x n, row-major)]; the mixer's and the FFN's own
        n_coeff = cfg.hc_mult * (2 + cfg.hc_mult)
        for tag in ("attn", "ffn"):
            norms.update({f"hc_{tag}_phi": (cfg.stream_width, n_coeff), f"hc_{tag}_alpha": (3,), f"hc_{tag}_bias": (n_coeff,)})
    di = cfg.s6_d_inner
    # an attending layer's query and output side, and its key and value side (a ``cross`` layer has the first alone)
    bias = cfg.attn_bias
    q_side = {"wq": (D, cfg.q_dim), "wo": (cfg.q_dim, D), **({"wq_b": (cfg.q_dim,), "wo_b": (D,)} if bias else {})}
    kv_side = {"wk": (D, cfg.kv_dim), "wv": (D, cfg.kv_dim), **({"wk_b": (cfg.kv_dim,), "wv_b": (cfg.kv_dim,)} if bias else {})}
    if cfg.diff_attn:  # lambda's four vectors and the norm over a pair's values [v1 | v2]
        q_side.update({n: (cfg.head_dim_,) for n in ("lq1", "lk1", "lq2", "lk2")}, sub_norm=(2 * cfg.head_dim_,))
    mixers = {
        "s6": {
            "in_proj": (D, 2 * di),  # [u | z]
            "conv_w": (cfg.mamba_d_conv, 1, di),  # as the Mamba-2 mixer's: tap k of channel c
            "conv_b": (di,),
            "x_proj": (di, cfg.s6_dt_rank + 2 * cfg.mamba_d_state),  # [r | B | C]
            "dt_proj": (cfg.s6_dt_rank, di),
            "dt_bias": (di,),
            "A_log": (cfg.mamba_d_state, di),  # the checkpoint's [channels, state], transposed: channels on the lanes
            "D": (di,),
            "out_proj": (di, D),
        },
        "swa": {**q_side, **kv_side},  # differential pairs, or plain grouped queries: the same four matrices
        "cross": dict(q_side),
        "gmu": {"gmu_in": (D, di), "gmu_out": (di, D)},
        "mamba": {
            "in_proj": (D, 2 * cfg.d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state + H),
            # the checkpoint's depthwise [C, 1, K] weight, reversed: tap k of
            # channel c is conv_w[k, 0, c]
            "conv_w": (cfg.mamba_d_conv, 1, C),
            "conv_b": (C,),
            "dt_bias": (H,),
            "A_log": (H,),
            "D": (H,),
            "ssm_norm": (cfg.d_inner,),
            "out_proj": (cfg.d_inner, D),
        },
        "attention": {**q_side, **kv_side} if cfg.diff_attn else {
            "wq": (D, cfg.q_dim),
            "wk": (D, cfg.kv_dim),
            "wv": (D, cfg.kv_dim),
            "wo": (cfg.q_dim, D),
            **({"wg": (D, cfg.q_dim)} if cfg.attn_gate else {}),
            **(
                {}
                if not cfg.qk_norm
                else {"q_norm": (cfg.head_dim_,), "k_norm": (cfg.head_dim_,)}
                if cfg.qk_norm_over == "head"
                else {"q_norm": (cfg.q_dim,), "k_norm": (cfg.kv_dim,)}
            ),
        },
        "conv": {
            "in_proj": (D, 3 * D),  # [B | C | x]
            "conv_w": (cfg.conv_L_cache, 1, D),  # as the Mamba mixer's: tap k of channel c
            "out_proj": (D, D),
        },
        "gdn": _gdn_shapes(cfg),
        "kda": _kda_shapes(cfg),
        "mla": {
            **(
                {
                    "w_qa": (D, cfg.q_lora_rank),
                    "q_a_norm": (cfg.q_lora_rank,),
                    "w_qb": (cfg.q_lora_rank, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                }
                if cfg.q_lora_rank
                else {"wq": (D, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))}
            ),
            **(
                {
                    # the index: its queries read the normed low-rank query, its ONE key and its head weights the layer's input
                    "wi_qb": (cfg.q_lora_rank, cfg.index_n_heads * cfg.index_head_dim),
                    "wi_k": (D, cfg.index_head_dim),
                    "wi_k_norm": (cfg.index_head_dim,),
                    "wi_k_norm_bias": (cfg.index_head_dim,),
                    "wi_w": (D, cfg.index_n_heads),
                }
                if cfg.index_topk
                else {}
            ),
            "w_kva": (D, cfg.latent_dim),  # [c | k_r]: the latent and the one rotary key
            "kv_norm": (cfg.kv_lora_rank,),
            # [k_nope | v] a head; the absorbed form slices W_UK and W_UV out of it
            "w_kvb": (cfg.kv_lora_rank, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (cfg.num_heads * cfg.v_head_dim, D),
        },
    }
    E, Fe, Fs = cfg.num_experts, cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
    ffns = {
        # [gate | up] is the granitemoehybrid checkpoint's fused input_linear
        "dense": {"w_gate_up": (D, 2 * F), "w_down": (F, D)}
        if cfg.fused_gate_up
        else {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)},
        # the router scores every expert of the layer; the stack holds this block's share
        "moe": {
            "w_router": (D, cfg.router_width),
            **({"router_bias": (cfg.router_width,)} if cfg.router_bias else {}),
            "we_gate": (E, D, Fe),
            "we_up": (E, D, Fe),
            "we_down": (E, Fe, D),
            **({"ws_gate": (D, Fs), "ws_up": (D, Fs), "ws_down": (Fs, D)} if Fs else {}),
        },
    }
    out: dict[str, dict[str, tuple[int, ...]]] = {}
    for kind, ffn in _layer_kinds(cfg):
        out.setdefault(stack_name(kind, ffn), {**norms, **ffns[ffn], **mixers[kind]})
    return out


def _gdn_shapes(cfg: HybridConfig) -> dict[str, tuple[int, ...]]:
    """One delta-rule mixer's leaves, as the ``fla`` layer has them: three
    projections each with a depthwise conv of its own (taps as the Mamba
    mixer's: tap k of channel c is ``*_conv_w[k, 0, c]``), the decay's and
    the write strength's projections a head, the output gate's, the norm
    over a head's values, the output projection."""
    D, H = cfg.hidden_size, cfg.gdn_n_heads
    qk, vd, kc = H * cfg.gdn_k_dim, H * cfg.gdn_v_dim, cfg.gdn_d_conv
    return {
        "q_proj": (D, qk),
        "k_proj": (D, qk),
        "v_proj": (D, vd),
        "a_proj": (D, H),
        "b_proj": (D, H),
        "g_proj": (D, vd),
        "q_conv_w": (kc, 1, qk),
        "k_conv_w": (kc, 1, qk),
        "v_conv_w": (kc, 1, vd),
        "A_log": (H,),
        "dt_bias": (H,),
        "o_norm": (cfg.gdn_v_dim,),
        "o_proj": (vd, D),
    }


def _kda_shapes(cfg: HybridConfig) -> dict[str, tuple[int, ...]]:
    """One ``kda`` mixer's leaves, as Kimi Linear's ``KimiDeltaAttention`` has
    them: three projections each with a depthwise conv of its own (taps as
    the Mamba mixer's), the decay's low-rank pair ``f_a`` / ``f_b`` with its
    bias a head and key channel and ``A_log`` a head, the write strength's
    projection a head, the output gate's low-rank pair ``g_a`` / ``g_b``, the
    norm over a head's values, the output projection."""
    D, H, r = cfg.hidden_size, cfg.kda_n_heads, cfg.kda_rank
    qk, vd, kc = H * cfg.kda_k_dim, H * cfg.kda_v_dim, cfg.kda_d_conv
    return {
        "q_proj": (D, qk),
        "k_proj": (D, qk),
        "v_proj": (D, vd),
        "f_a": (D, r),
        "f_b": (r, qk),
        "b_proj": (D, H),
        "g_a": (D, r),
        "g_b": (r, vd),
        "q_conv_w": (kc, 1, qk),
        "k_conv_w": (kc, 1, qk),
        "v_conv_w": (kc, 1, vd),
        "A_log": (H,),
        "dt_bias": (qk,),
        "o_norm": (cfg.kda_v_dim,),
        "o_proj": (vd, D),
    }


def _stack_sizes(cfg: HybridConfig) -> dict[str, int]:
    sizes: dict[str, int] = {}
    for kind, ffn in _layer_kinds(cfg):
        sizes[stack_name(kind, ffn)] = sizes.get(stack_name(kind, ffn), 0) + 1
    return sizes


def init_params(rng: jax.Array, cfg: HybridConfig, dtype=None) -> dict:
    """Random init, stacked per kind of layer. ``A``, ``dt`` and ``D`` as the
    published Mamba-2 initialisation draws them (A uniform in 1-16, dt
    log-uniform in 0.001-0.1 through the inverse softplus, D = 1)."""
    dtype = dtype or cfg.jax_dtype
    keys = iter(jax.random.split(rng, 128))

    def dense(shape):
        return (0.02 * jax.random.truncated_normal(next(keys), -2, 2, shape, jnp.float32)).astype(dtype)

    params: dict[str, Any] = {
        "embed": dense((cfg.vocab_size, cfg.hidden_size)),
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if cfg.norm_kind == "layer" and cfg.norm_bias:
        params["final_norm_bias"] = jnp.zeros((cfg.hidden_size,), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense((cfg.vocab_size, cfg.hidden_size))
    sizes = _stack_sizes(cfg)
    for name, shapes in _layer_shapes(cfg).items():
        stack = {}
        for leaf, shape in shapes.items():
            full = (sizes[name], *shape)
            if leaf.endswith("norm") or leaf == "D":
                stack[leaf] = jnp.ones(full, dtype)
            elif leaf in ("conv_b", "wi_k_norm_bias") or leaf.endswith(("_norm_bias", "_b")):
                stack[leaf] = jnp.zeros(full, dtype)
            elif leaf.startswith("hc_") and leaf.endswith("_alpha"):  # the hyper-connections papers start the gains near 0
                stack[leaf] = jnp.full(full, 0.01, dtype)
            elif leaf in ("lq1", "lk1", "lq2", "lk2"):  # lambda's vectors: N(0, 0.1), as the Differential Transformer draws them
                stack[leaf] = (0.1 * jax.random.normal(next(keys), full, jnp.float32)).astype(dtype)
            elif leaf == "A_log":
                stack[leaf] = jnp.log(jax.random.uniform(next(keys), full, jnp.float32, 1.0, 16.0)).astype(dtype)
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(next(keys), full, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                stack[leaf] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
            else:
                stack[leaf] = dense(full)
        params[name] = stack
    return params


def param_partition_specs(cfg: HybridConfig, fsdp_axis: str | None = "fsdp") -> dict:
    """Every leaf replicated: this family serves on one chip per replica. A
    mixer and a recurrent state sharded over the ``model`` axis (heads), or
    experts over chips, is ROADMAP Reach A.7."""
    del fsdp_axis
    specs: dict[str, Any] = {"embed": P(), "final_norm": P()}
    if cfg.norm_kind == "layer" and cfg.norm_bias:
        specs["final_norm_bias"] = P()
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P()
    for name, shapes in _layer_shapes(cfg).items():
        specs[name] = {leaf: P() for leaf in shapes}
    return specs


# our leaf -> (checkpoint name within ``model.layers.N.``, transpose), by
# model_type. ``olmo_hybrid``: the block's and the attention layer's names are
# ``transformers``' ``Olmo3`` classes' (tests/test_olmo_hybrid_parity.py holds
# the block to ``Olmo3DecoderLayer``); the names under ``linear_attn.`` are the
# ``fla`` layer's attributes and could NOT be checked here (no network, and the
# installed transformers 4.57.6 has no ``olmo_hybrid``). ``lfm2_moe``: the mixers, norms, dense MLP and tied head are
# held to ``transformers``' ``Lfm2`` classes (tests/test_lfm2_hf_parity.py);
# the names under ``feed_forward.gate`` / ``.expert_bias`` / ``.experts.E``
# could NOT be checked here (no network, and the installed transformers has
# ``lfm2`` but not ``lfm2_moe``): they are the published repository's as the
# ISSUE of PR 30 wrote them down.
_HF_LAYER_MAPS = {
    "granitemoehybrid": {
        "input_norm": ("input_layernorm.weight", False),
        "post_norm": ("post_attention_layernorm.weight", False),
        "w_gate_up": ("shared_mlp.input_linear.weight", True),
        "w_down": ("shared_mlp.output_linear.weight", True),
        "in_proj": ("mamba.in_proj.weight", True),
        "conv_w": ("mamba.conv1d.weight", True),
        "conv_b": ("mamba.conv1d.bias", False),
        "dt_bias": ("mamba.dt_bias", False),
        "A_log": ("mamba.A_log", False),
        "D": ("mamba.D", False),
        "ssm_norm": ("mamba.norm.weight", False),
        "out_proj": ("mamba.out_proj.weight", True),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
    },
    "lfm2_moe": {
        "input_norm": ("operator_norm.weight", False),
        "post_norm": ("ffn_norm.weight", False),
        "w_gate": ("feed_forward.w1.weight", True),
        "w_up": ("feed_forward.w3.weight", True),
        "w_down": ("feed_forward.w2.weight", True),
        "in_proj": ("conv.in_proj.weight", True),
        "conv_w": ("conv.conv.weight", True),
        "out_proj": ("conv.out_proj.weight", True),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.out_proj.weight", True),
        "q_norm": ("self_attn.q_layernorm.weight", False),
        "k_norm": ("self_attn.k_layernorm.weight", False),
        "w_router": ("feed_forward.gate.weight", True),
        "router_bias": ("feed_forward.expert_bias", False),
        # one tensor per expert: feed_forward.experts.E.<name>
        "we_gate": ("feed_forward.experts.{e}.w1.weight", True),
        "we_up": ("feed_forward.experts.{e}.w3.weight", True),
        "we_down": ("feed_forward.experts.{e}.w2.weight", True),
    },
    "olmo_hybrid": {
        # the block's two norms stand on the sublayers' outputs (norm_placement)
        "input_norm": ("post_attention_layernorm.weight", False),
        "post_norm": ("post_feedforward_layernorm.weight", False),
        "w_gate": ("mlp.gate_proj.weight", True),
        "w_up": ("mlp.up_proj.weight", True),
        "w_down": ("mlp.down_proj.weight", True),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "q_norm": ("self_attn.q_norm.weight", False),
        "k_norm": ("self_attn.k_norm.weight", False),
        "q_proj": ("linear_attn.q_proj.weight", True),
        "k_proj": ("linear_attn.k_proj.weight", True),
        "v_proj": ("linear_attn.v_proj.weight", True),
        "a_proj": ("linear_attn.a_proj.weight", True),
        "b_proj": ("linear_attn.b_proj.weight", True),
        "g_proj": ("linear_attn.g_proj.weight", True),
        "q_conv_w": ("linear_attn.q_conv1d.weight", True),
        "k_conv_w": ("linear_attn.k_conv1d.weight", True),
        "v_conv_w": ("linear_attn.v_conv1d.weight", True),
        "A_log": ("linear_attn.A_log", False),
        "dt_bias": ("linear_attn.dt_bias", False),
        "o_norm": ("linear_attn.o_norm.weight", False),
        "o_proj": ("linear_attn.o_proj.weight", True),
    },
    "deepseek_v3": {
        # held to ``transformers``' ``DeepseekV3ForCausalLM`` (tests/test_kanana2_hf_parity.py)
        "input_norm": ("input_layernorm.weight", False),
        "post_norm": ("post_attention_layernorm.weight", False),
        "wq": ("self_attn.q_proj.weight", True),
        "w_qa": ("self_attn.q_a_proj.weight", True),
        "q_a_norm": ("self_attn.q_a_layernorm.weight", False),
        "w_qb": ("self_attn.q_b_proj.weight", True),
        # the index, as DeepSeek-V3.2's published code names it (no checkpoint could be read here)
        "wi_qb": ("self_attn.indexer.wq_b.weight", True),
        "wi_k": ("self_attn.indexer.wk.weight", True),
        "wi_k_norm": ("self_attn.indexer.k_norm.weight", False),
        "wi_k_norm_bias": ("self_attn.indexer.k_norm.bias", False),
        "wi_w": ("self_attn.indexer.weights_proj.weight", True),
        "w_kva": ("self_attn.kv_a_proj_with_mqa.weight", True),
        "kv_norm": ("self_attn.kv_a_layernorm.weight", False),
        "w_kvb": ("self_attn.kv_b_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "w_gate": ("mlp.gate_proj.weight", True),
        "w_up": ("mlp.up_proj.weight", True),
        "w_down": ("mlp.down_proj.weight", True),
        "w_router": ("mlp.gate.weight", True),
        "router_bias": ("mlp.gate.e_score_correction_bias", False),
        "we_gate": ("mlp.experts.{e}.gate_proj.weight", True),
        "we_up": ("mlp.experts.{e}.up_proj.weight", True),
        "we_down": ("mlp.experts.{e}.down_proj.weight", True),
        "ws_gate": ("mlp.shared_experts.gate_proj.weight", True),
        "ws_up": ("mlp.shared_experts.up_proj.weight", True),
        "ws_down": ("mlp.shared_experts.down_proj.weight", True),
    },
}
_HF_LAYER_MAPS["glm_moe_dsa"] = _HF_LAYER_MAPS["deepseek_v3"]  # the same block; the index's names are in it
# ``phi4flash``: UNCHECKED (no network, and the installed transformers has no ``phi4flash``). The names are the
# family's released ``modeling_phi4flash.py`` as ISSUE 43 reads it: ``attn`` (``Wqkv`` fused there: a loader splits
# it into the three names below, the query's rows first), ``attn.inner_cross_attn.*`` for lambda's vectors and the
# norm over a pair's values, ``attn`` again for a Mamba layer's mixer (the block calls either ``attn``), ``mlp.fc1``
# the fused [gate | up]; a memory unit's two matrices are the layer's ``attn.in_proj`` / ``attn.out_proj``.
_HF_LAYER_MAPS["phi4flash"] = {
    "input_norm": ("input_layernorm.weight", False),
    "input_norm_bias": ("input_layernorm.bias", False),
    "post_norm": ("post_attention_layernorm.weight", False),
    "post_norm_bias": ("post_attention_layernorm.bias", False),
    "w_gate_up": ("mlp.fc1.weight", True),
    "w_down": ("mlp.fc2.weight", True),
    "in_proj": ("attn.in_proj.weight", True),
    "conv_w": ("attn.conv1d.weight", True),
    "conv_b": ("attn.conv1d.bias", False),
    "x_proj": ("attn.x_proj.weight", True),
    "dt_proj": ("attn.dt_proj.weight", True),
    "dt_bias": ("attn.dt_proj.bias", False),
    "A_log": ("attn.A_log", True),
    "D": ("attn.D", False),
    "out_proj": ("attn.out_proj.weight", True),
    "wq": ("attn.Wqkv.q.weight", True),
    "wk": ("attn.Wqkv.k.weight", True),
    "wv": ("attn.Wqkv.v.weight", True),
    "wq_b": ("attn.Wqkv.q.bias", False),
    "wk_b": ("attn.Wqkv.k.bias", False),
    "wv_b": ("attn.Wqkv.v.bias", False),
    "wo": ("attn.out_proj.weight", True),
    "wo_b": ("attn.out_proj.bias", False),
    "lq1": ("attn.inner_cross_attn.lambda_q1", False),
    "lk1": ("attn.inner_cross_attn.lambda_k1", False),
    "lq2": ("attn.inner_cross_attn.lambda_q2", False),
    "lk2": ("attn.inner_cross_attn.lambda_k2", False),
    "sub_norm": ("attn.inner_cross_attn.subln.weight", False),
    "gmu_in": ("attn.in_proj.weight", True),
    "gmu_out": ("attn.out_proj.weight", True),
}
# ``solar_open2``: UNCHECKED against a checkpoint (no network, no ``config.json`` or weights of the family on this
# machine, and the installed transformers has no ``solar_open2``). The delta-rule layer's names are Kimi Linear's
# ``KimiDeltaAttention`` attributes under ``self_attn``, the attention layer's gate ``self_attn.g_proj``, the expert
# block's the DeepSeek-V3 lineage's, as ISSUE 47 reads the configuration's keys; ``q_proj`` / ``k_proj`` / ``v_proj``
# / ``o_proj`` of a delta-rule layer and ``wq`` ... of an attention layer both sit under ``self_attn`` there.
_HF_LAYER_MAPS["solar_open2"] = {
    **{k: v for k, v in _HF_LAYER_MAPS["deepseek_v3"].items() if k in (
        "input_norm", "post_norm", "wq", "wo", "w_gate", "w_up", "w_down", "w_router", "router_bias",
        "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
    )},
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wg": ("self_attn.g_proj.weight", True),
    "q_proj": ("self_attn.q_proj.weight", True),
    "k_proj": ("self_attn.k_proj.weight", True),
    "v_proj": ("self_attn.v_proj.weight", True),
    "f_a": ("self_attn.f_a_proj.weight", True),
    "f_b": ("self_attn.f_b_proj.weight", True),
    "b_proj": ("self_attn.b_proj.weight", True),
    "g_a": ("self_attn.g_a_proj.weight", True),
    "g_b": ("self_attn.g_b_proj.weight", True),
    "q_conv_w": ("self_attn.q_conv1d.weight", True),
    "k_conv_w": ("self_attn.k_conv1d.weight", True),
    "v_conv_w": ("self_attn.v_conv1d.weight", True),
    "A_log": ("self_attn.A_log", False),
    "dt_bias": ("self_attn.dt_bias", False),
    "o_norm": ("self_attn.o_norm.weight", False),
    "o_proj": ("self_attn.o_proj.weight", True),
}
# ``cohere2_moe``: UNCHECKED against a checkpoint (no network, no ``config.json`` or weights of the family on this
# machine, and the installed transformers has ``cohere2`` but no ``cohere2_moe``). The block's and the attention
# layer's names are ``transformers``' ``Cohere2DecoderLayer`` / ``Cohere2Attention`` attributes (ONE
# ``input_layernorm``, no second norm); the expert block's are the DeepSeek-V3 lineage's under ``mlp`` as ISSUE 51
# reads the configuration's keys, with the FOUR shared experts as four blocks ``mlp.shared_experts.{s}.*`` (the leaf
# holds them side by side: shared expert s is columns s * width .. of ``ws_gate`` / ``ws_up``, rows of ``ws_down``).
_HF_LAYER_MAPS["cohere2_moe"] = {
    "input_norm": ("input_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "w_router": ("mlp.gate.weight", True),
    "we_gate": ("mlp.experts.{e}.gate_proj.weight", True),
    "we_up": ("mlp.experts.{e}.up_proj.weight", True),
    "we_down": ("mlp.experts.{e}.down_proj.weight", True),
    "ws_gate": ("mlp.shared_experts.{s}.gate_proj.weight", True),
    "ws_up": ("mlp.shared_experts.{s}.up_proj.weight", True),
    "ws_down": ("mlp.shared_experts.{s}.down_proj.weight", True),
}
# ``xing4_0``: UNCHECKED against a checkpoint (no network, no ``config.json``, weights or modelling code of the family
# on this machine, and the installed transformers has no ``xing4_0``). The block's names are the DeepSeek-V3 lineage's,
# as the configuration's keys are; the stream coefficients' names (``attn_hc`` / ``mlp_hc`` with ``phi``, ``alpha``,
# ``bias``) are this module's GUESS at a layout and nothing published: a loader for a real checkpoint starts here.
_HF_LAYER_MAPS["xing4_0"] = {
    **{k: v for k, v in _HF_LAYER_MAPS["deepseek_v3"].items() if not k.startswith("wi_")},
    "hc_attn_phi": ("attn_hc.phi.weight", True),
    "hc_attn_alpha": ("attn_hc.alpha", False),
    "hc_attn_bias": ("attn_hc.bias", False),
    "hc_ffn_phi": ("mlp_hc.phi.weight", True),
    "hc_ffn_alpha": ("mlp_hc.alpha", False),
    "hc_ffn_bias": ("mlp_hc.bias", False),
}
_HF_TOP = {
    "xing4_0": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "cohere2_moe": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "solar_open2": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "deepseek_v3": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "glm_moe_dsa": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "granitemoehybrid": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "lfm2_moe": {"embed": "model.embed_tokens.weight", "final_norm": "model.embedding_norm.weight"},
    "olmo_hybrid": {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"},
    "phi4flash": {
        "embed": "model.embed_tokens.weight", "final_norm": "model.final_layernorm.weight",
        "final_norm_bias": "model.final_layernorm.bias",
    },
}


def hf_name_map(cfg: HybridConfig) -> dict[str, tuple[str, bool]]:
    """Our param path -> (checkpoint name of ``cfg.model_type``, transpose).
    A stacked leaf maps as ``<stack>/<index within the stack>/<name>``, an
    expert's as ``.../<name>/<expert within the stack>`` (the checkpoint's
    expert ``cfg.expert_first`` + that), and where the always-active block
    is several shared experts side by side each of them as
    ``.../<name>/s<shared expert>`` (``models/hf.py`` lays those side by
    side along the leaf's wide axis); the checkpoint numbers layers in the
    order of ``layer_types``."""
    out: dict[str, tuple[str, bool]] = {k: (v, False) for k, v in _HF_TOP[cfg.model_type].items()}
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ("lm_head.weight", False)
    layer_map = _HF_LAYER_MAPS[cfg.model_type]
    seen: dict[str, int] = {}
    shapes = _layer_shapes(cfg)
    for i, (kind, ffn) in enumerate(_layer_kinds(cfg)):
        stack = stack_name(kind, ffn)
        n = seen.get(stack, 0)
        for name in shapes[stack]:
            suffix, transpose = layer_map[name]
            if "{e}" in suffix:
                for e in range(cfg.num_experts):
                    ckpt = suffix.format(e=cfg.expert_first + e)
                    out[f"{stack}/{n}/{name}/{e}"] = (f"model.layers.{i}.{ckpt}", transpose)
            elif "{s}" in suffix:  # the shared experts side by side in one leaf: block s of its columns (``ws_down``: rows)
                for sh in range(cfg.moe_shared_mean_of):
                    out[f"{stack}/{n}/{name}/s{sh}"] = (f"model.layers.{i}.{suffix.format(s=sh)}", transpose)
            else:
                out[f"{stack}/{n}/{name}"] = (f"model.layers.{i}.{suffix}", transpose)
        seen[stack] = n + 1
    return out


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------


def _split_xbc(cfg: HybridConfig, xbc: jax.Array):
    """[..., conv_dim] -> x [..., H, P], B and C [..., G, N], in float32."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    x, b, c = jnp.split(xbc.astype(jnp.float32), [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    lead = xbc.shape[:-1]
    x = x.reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    b = b.reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    c = c.reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    return x, b, c


def _per_head(cfg: HybridConfig, bc: jax.Array, axis: int) -> jax.Array:
    """Group-wise B or C [..., G, N] -> per head [..., H, N] along ``axis``."""
    return jnp.repeat(bc, cfg.mamba_n_heads // cfg.mamba_n_groups, axis=axis)


def _dt_a(layer: dict, dt_raw: jax.Array):
    """Raw dt [..., H] -> (dt after softplus, A per head), float32. Mamba-2's
    ``time_step_limit`` is (0, inf) in this family: nothing is clamped."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(layer["A_log"].astype(jnp.float32))


def ssm_decode_step(cfg: HybridConfig, layer: dict, ssm, xbc, dt_raw, active):
    """The recurrence, one token for each of S slots.

    ssm [S, H, P, N] (its own dtype, computed in float32), xbc [S, conv_dim]
    after the conv, dt_raw [S, H]. Returns (new state, y [S, d_inner] f32).
    A slot that is not ``active`` keeps its state bit for bit."""
    x, b, c = _split_xbc(cfg, xbc)
    b, c = _per_head(cfg, b, 1), _per_head(cfg, c, 1)  # [S, H, N]
    dt, a = _dt_a(layer, dt_raw)
    s32 = ssm.astype(jnp.float32)
    new = s32 * jnp.exp(dt * a)[..., None, None] + (dt[..., None] * x)[..., None] * b[:, :, None, :]
    y = jnp.sum(new * c[:, :, None, :], axis=-1) + layer["D"].astype(jnp.float32)[None, :, None] * x
    new = jnp.where(active[:, None, None, None], new.astype(ssm.dtype), ssm)
    return new, y.reshape(y.shape[0], cfg.d_inner)


def ssm_chunked_scan(cfg: HybridConfig, layer: dict, xbc, dt_raw, n_state, state_dtype=jnp.float32):
    """The chunked algorithm for the same recurrence over whole prompts.

    xbc [A, L, conv_dim] after the conv, dt_raw [A, L, H], n_state [A]: only
    the first ``n_state`` tokens of a row enter its state (``dt`` is 0 from
    there on, so the state neither decays nor takes input; ``y`` at those
    positions is then not the model's and must not be used). Starts from the
    zero state. Returns (state after n_state tokens [A, H, P, N], y
    [A, L, d_inner] float32)."""
    A, L, _ = xbc.shape
    Q = min(cfg.mamba_chunk_size, L)
    pad = (-L) % Q
    H, Pd, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    x, b, c = _split_xbc(cfg, xbc)
    dt, a = _dt_a(layer, dt_raw)
    dt = jnp.where(jnp.arange(L)[None, :, None] < n_state[:, None, None], dt, 0.0)
    if pad:
        x, b, c, dt = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, b, c, dt))
    nc = (L + pad) // Q
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    hi = jax.lax.Precision.HIGHEST
    d_skip = layer["D"].astype(jnp.float32)

    def chunk(i, carry):
        s_in, y_all = carry
        x_c, dt_c, b_c, c_c = (jax.lax.dynamic_slice_in_dim(t, i * Q, Q, axis=1) for t in (x, dt, b, c))
        # [A,Q,H,P] [A,Q,H] [A,Q,G,N] [A,Q,G,N]
        b_h, c_h = _per_head(cfg, b_c, 2), _per_head(cfg, c_c, 2)  # [A,Q,H,N]
        a_cum = jnp.cumsum(dt_c * a, axis=1)  # [A,Q,H], <= 0 and falling
        seg = a_cum[:, :, None, :] - a_cum[:, None, :, :]  # [A, t, s, H]
        decay = jnp.exp(jnp.where(tril[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("athn,ashn->atsh", c_h, b_h, precision=hi)
        w = cb * decay * dt_c[:, None, :, :]
        y = jnp.einsum("atsh,ashp->athp", w, x_c, precision=hi)
        y = y + jnp.einsum("athn,ahpn->athp", c_h, s_in, precision=hi) * jnp.exp(a_cum)[..., None]
        y = y + d_skip[None, None, :, None] * x_c
        to_end = jnp.exp(a_cum[:, -1:, :] - a_cum) * dt_c  # [A,Q,H]
        s_out = s_in * jnp.exp(a_cum[:, -1, :])[..., None, None] + jnp.einsum(
            "ashp,ashn->ahpn", to_end[..., None] * x_c, b_h, precision=hi
        )
        return s_out, jax.lax.dynamic_update_slice_in_dim(y_all, y, i * Q, axis=1)

    carry = (jnp.zeros((A, H, Pd, N), jnp.float32), jnp.zeros((A, L + pad, H, Pd), jnp.float32))
    s_fin, y = jax.lax.fori_loop(0, nc, chunk, carry)
    y = y.reshape(A, L + pad, cfg.d_inner)[:, :L]
    return s_fin.astype(state_dtype), y


def _window_after(padded, n_state, K: int):
    """The conv inputs of the last K-1 of each row's first ``n_state`` tokens,
    flat [A, (K-1) * channels] float32, from ``padded`` [A, K-1+L, channels]
    (position t at row t + K - 1, zeros before the prompt): tokens
    n_state-K+1 .. n_state-1 = padded rows n_state .. n_state+K-2, picked by
    a one-hot product (exact: one term a sum) and not by a gather."""
    A, rows_n, _ = padded.shape
    rows = n_state[:, None] + jnp.arange(K - 1)[None, :]
    pick = (rows[:, :, None] == jnp.arange(rows_n)[None, None, :]).astype(padded.dtype)
    return jnp.einsum("akt,atc->akc", pick, padded, preferred_element_type=jnp.float32).reshape(A, -1)


def _conv_window_step(conv, raw, w, bias, active):
    """One decode step of a depthwise conv over a slot's window, in one pass
    over the row as the state stores it. ``conv`` [S, (K-1) * C] (a slot's
    last K-1 conv inputs, oldest first, in the state's type), ``raw`` [S, C]
    (this token's input, rounded here to the window's type: both forms
    convolve the values a slot's window holds), ``w`` [K, C] and ``bias``
    [C] or None, float32. Returns (acc [S, C] float32, accumulated in the
    prefill forms' order, and the window shifted by one token; rows that are
    not ``active`` keep theirs bit for bit).

    The taps are lane slices of the row, never a [S, K, C] array: a second-
    minor dimension of K is padded to the chip's tile and every element
    moves, twice a layer (tests/test_tpu_compile.py holds the compiled text
    to that)."""
    C = raw.shape[-1]
    taps = [conv[:, k * C : (k + 1) * C] for k in range(w.shape[0] - 1)] + [raw.astype(conv.dtype)]
    acc = 0.0 if bias is None else bias
    for k, tap in enumerate(taps):
        acc = acc + tap.astype(jnp.float32) * w[k]
    return acc, jnp.where(active[:, None], jnp.concatenate(taps[1:], axis=-1), conv)


def _conv_taps(layer: dict):
    return layer["conv_w"][:, 0, :].astype(jnp.float32), layer["conv_b"].astype(jnp.float32)


def _gated_out(cfg: HybridConfig, layer: dict, y, z, dtype):
    """rmsnorm(y * silu(z)) per group of channels, then the out projection."""
    g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    lead = g.shape[:-1]
    g = g.reshape(*lead, cfg.mamba_n_groups, -1)
    w = layer["ssm_norm"].reshape(cfg.mamba_n_groups, -1)
    return _rms_norm(g, w, cfg.rms_norm_eps).reshape(*lead, cfg.d_inner)


def _mamba_in(cfg: HybridConfig, layer: dict, h):
    zxbcdt = _proj(cfg, layer, "in_proj", h)
    return jnp.split(zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)


def mamba_decode(cfg: HybridConfig, layer: dict, h, state: dict, j, active, live=None):
    """Mixer for one token a slot. h [S, D] (normed); ``state`` holds every
    Mamba layer's slot state, ``ssm`` [n, S, H, P, N] and ``conv``
    [n, S, (K-1) * conv_dim] (the raw conv inputs of the last K-1 tokens,
    oldest first), of which this is layer ``j``. Returns (out [S, D], the
    state with layer j advanced); rows that are not ``active`` keep theirs.

    ``live`` = ``paged_attention_q8.live_order(active)`` runs the recurrence
    in the Pallas kernel, which reads and writes the live slots' SSM state
    only and in place; without it ``ssm_decode_step`` passes over all slots
    under a mask (off a TPU, and the form the tests hold the kernel to)."""
    S = h.shape[0]
    conv = jax.lax.dynamic_index_in_dim(state["conv"], j, 0, keepdims=False)
    with jax.named_scope("ssm_proj"):
        z, raw, dt_raw = _mamba_in(cfg, layer, h)
    with jax.named_scope("ssm_conv"):
        acc, new_conv = _conv_window_step(conv, raw, *_conv_taps(layer), active)
        xbc = jax.nn.silu(acc)
    with jax.named_scope("ssm_state"):
        if live is None:
            ssm = jax.lax.dynamic_index_in_dim(state["ssm"], j, 0, keepdims=False)
            ssm, y = ssm_decode_step(cfg, layer, ssm, xbc, dt_raw, active)
        else:
            from areal_tpu.ops.ssm_state_update import ssm_state_update_stacked

            x, b, c = _split_xbc(cfg, xbc)
            dt, a = _dt_a(layer, dt_raw)
            ssm_all, y = ssm_state_update_stacked(state["ssm"], j, x, b, c, dt, a, *live)
            y = (y + layer["D"].astype(jnp.float32)[None, :, None] * x).reshape(S, cfg.d_inner)
        g = _gated_out(cfg, layer, y, z, h.dtype)
    with jax.named_scope("state_write"):
        if live is None:
            ssm_all = jax.lax.dynamic_update_index_in_dim(state["ssm"], ssm, j, 0)
        state = {"ssm": ssm_all, "conv": jax.lax.dynamic_update_index_in_dim(state["conv"], new_conv, j, 0)}
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), state


def mamba_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtypes):
    """Mixer over whole prompts. h [A, L, D] (normed), n_state [A]. Returns
    (out [A, L, D], ssm state after n_state tokens, conv window of the last
    K-1 of those tokens; positions before the prompt count as zeros)."""
    A, L, _ = h.shape
    K = cfg.mamba_d_conv
    with jax.named_scope("ssm_proj"):
        z, raw, dt_raw = _mamba_in(cfg, layer, h)
    with jax.named_scope("ssm_conv"):
        w, bias = _conv_taps(layer)
        padded = jnp.pad(raw, ((0, 0), (K - 1, 0), (0, 0)))  # position t at row t + K - 1
        acc = bias
        for k in range(K):
            acc = acc + padded[:, k : k + L].astype(jnp.float32) * w[k]
        xbc = jax.nn.silu(acc)
        conv = _window_after(padded, n_state, K)
    with jax.named_scope("ssm_state"):
        ssm, y = ssm_chunked_scan(cfg, layer, xbc, dt_raw, n_state, state_dtypes[0])
        g = _gated_out(cfg, layer, y, z, h.dtype)
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), ssm, conv.astype(state_dtypes[1])


# ---------------------------------------------------------------------------
# the short-conv mixer
# ---------------------------------------------------------------------------


def _conv_gates(cfg: HybridConfig, layer: dict, h, state_dtype):
    """(g = B * x rounded to the type the window keeps it in, C), both
    [..., D]: both forms convolve the values a slot's state would hold."""
    with jax.named_scope("conv_proj"):
        b, c, x = jnp.split(_proj(cfg, layer, "in_proj", h), 3, axis=-1)
    return (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(state_dtype), c


def conv_decode(cfg: HybridConfig, layer: dict, h, conv_all, j, active):
    """Mixer for one token a slot. h [S, D] (normed); ``conv_all`` [n, S,
    (K-1) * D] holds every conv layer's window (a slot's last K-1 values of
    ``g``, oldest first), of which this is layer ``j``. Returns (out [S, D],
    the windows with layer j advanced); rows that are not ``active`` keep
    theirs bit for bit."""
    conv = jax.lax.dynamic_index_in_dim(conv_all, j, 0, keepdims=False)
    g, c = _conv_gates(cfg, layer, h, conv.dtype)
    with jax.named_scope("conv_mix"):
        acc, new_conv = _conv_window_step(conv, g, layer["conv_w"][:, 0, :].astype(jnp.float32), None, active)
        y = (c.astype(jnp.float32) * acc).astype(h.dtype)
    with jax.named_scope("state_write"):
        conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, new_conv, j, 0)
    with jax.named_scope("conv_proj"):
        return _proj(cfg, layer, "out_proj", y), conv_all


def conv_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtype):
    """Mixer over whole prompts. h [A, L, D] (normed), n_state [A]. Returns
    (out [A, L, D], the window after each row's first n_state tokens
    [A, (K-1) * D]; positions before the prompt count as zeros)."""
    A, L, D = h.shape
    K = cfg.conv_L_cache
    g, c = _conv_gates(cfg, layer, h, state_dtype)
    with jax.named_scope("conv_mix"):
        w = layer["conv_w"][:, 0, :].astype(jnp.float32)
        padded = jnp.pad(g, ((0, 0), (K - 1, 0), (0, 0)))  # position t at row t + K - 1
        acc = 0.0
        for k in range(K):
            acc = acc + padded[:, k : k + L].astype(jnp.float32) * w[k]
        y = (c.astype(jnp.float32) * acc).astype(h.dtype)
        conv = _window_after(padded, n_state, K).astype(state_dtype)
    with jax.named_scope("conv_proj"):
        return _proj(cfg, layer, "out_proj", y), conv


# ---------------------------------------------------------------------------
# the gated-delta-rule mixer
# ---------------------------------------------------------------------------


def _gdn_in(cfg: HybridConfig, layer: dict, h):
    """The projections of h [..., D]: (the convs' input [q | k | v]
    [..., gdn_conv_dim], the output gate's [..., H * V], the log decay g
    [..., H] <= 0 and the write strength beta [..., H], both float32)."""
    with jax.named_scope("gdn_proj"):
        raw = jnp.concatenate([_proj(cfg, layer, n, h) for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
        z = _proj(cfg, layer, "g_proj", h)
        a = _proj(cfg, layer, "a_proj", h).astype(jnp.float32)
        b = _proj(cfg, layer, "b_proj", h).astype(jnp.float32)
        g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(a + layer["dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(b) * (2.0 if cfg.gdn_neg_eigval else 1.0)
    return raw, z, g, beta


def _gdn_taps(layer: dict):
    """The three convs' taps side by side, [taps, gdn_conv_dim] float32."""
    return jnp.concatenate([layer[n][:, 0, :] for n in ("q_conv_w", "k_conv_w", "v_conv_w")], axis=-1).astype(jnp.float32)


def _gdn_heads(cfg: HybridConfig, qkv):
    """[..., gdn_conv_dim] float32 after conv and SiLU -> q and k [..., H, K]
    L2-normalised (q also scaled by K^-1/2), v [..., H, V]."""
    return _delta_heads(qkv, cfg.gdn_n_heads, cfg.gdn_k_dim, cfg.gdn_v_dim)


def _delta_heads(qkv, H: int, K: int, V: int):
    """A delta-rule mixer's [q | k | v] after conv and SiLU, split into heads
    (``_gdn_heads``, ``_kda_heads``)."""
    q, k, v = jnp.split(qkv, [H * K, 2 * H * K], axis=-1)
    lead = qkv.shape[:-1]
    q, k, v = q.reshape(*lead, H, K), k.reshape(*lead, H, K), v.reshape(*lead, H, V)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * K**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    return q, k, v


def _gdn_out(cfg: HybridConfig, layer: dict, o, z, dtype):
    """rmsnorm over each head's values, times silu(gate): [..., H * V]."""
    lead = o.shape[:-2]
    z = z.astype(jnp.float32).reshape(*lead, cfg.gdn_n_heads, cfg.gdn_v_dim)
    y = _rms_norm(o, layer["o_norm"].astype(jnp.float32), cfg.rms_norm_eps) * jax.nn.silu(z)
    return y.reshape(*lead, -1).astype(dtype)


def gdn_decode_step(state, q, k, v, alpha, beta, active):
    """The recurrence, one token for each of S slots: ``S' = alpha S``,
    ``u = beta (v - S'^T k)``, ``S = S' + k u^T``, ``o = S^T q``.

    state [S, H, K, V] (its own dtype, computed in float32), q and k
    [S, H, K], v [S, H, V], alpha and beta [S, H]. Returns (new state,
    o [S, H, V] float32). A slot that is not ``active`` keeps its state bit
    for bit."""
    decayed = state.astype(jnp.float32) * alpha[..., None, None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., :, None], axis=-2))
    new = decayed + k[..., :, None] * u[..., None, :]
    o = jnp.sum(new * q[..., :, None], axis=-2)
    return jnp.where(active[:, None, None, None], new.astype(state.dtype), state), o


def _unit_lower_inverse(m, base: int = 16):
    """(I + strictly-lower(m))^-1 for m [..., C, C], C a multiple of ``base``
    a power of two times: forward substitution row by row inside the
    ``base`` x ``base`` diagonal blocks, then [[A, 0], [B, D]]^-1 =
    [[A^-1, 0], [-D^-1 B A^-1, D^-1]] block by block, so that most of the
    work is matmuls. Both are the substitution itself, not a power series
    in the nilpotent part (whose terms grow with the keys' overlap and
    cancel)."""
    C = m.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    low = jnp.tril(m, -1)
    nb = C // base
    blocks = low.reshape(*m.shape[:-2], nb, base, nb, base)
    idx = jnp.arange(nb)
    n = -jnp.moveaxis(blocks[..., idx, :, idx, :], 0, -3)  # [..., nb, base, base]: -L of the diagonal blocks
    for i in range(1, base):  # row i of N = -L_i + (-L_i) N, rows above it final
        row = n[..., i, :]
        n = n.at[..., i, :].set(row + jnp.einsum("...j,...jk->...k", row, n, precision=hi))
    inv = n + jnp.eye(base, dtype=m.dtype)  # [..., nb, base, base]
    size = base
    while size < C:
        nb //= 2
        pairs = inv.reshape(*inv.shape[:-3], nb, 2, size, size)
        a_inv, d_inv = pairs[..., 0, :, :], pairs[..., 1, :, :]
        lo = low.reshape(*m.shape[:-2], nb, 2 * size, nb, 2 * size)
        b = jnp.moveaxis(lo[..., idx[:nb], size:, idx[:nb], :size], 0, -3)  # below-left block of each pair
        c = -jnp.einsum("...ij,...jk,...kl->...il", d_inv, b, a_inv, precision=hi)
        top = jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([c, d_inv], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def gdn_chunked_scan(q, k, v, g, beta, n_state, state_dtype=jnp.float32):
    """The chunked (WY) algorithm for the same recurrence over whole prompts.

    q and k [A, L, H, K], v [A, L, H, V], g and beta [A, L, H], n_state [A]:
    only the first ``n_state`` tokens of a row enter its state (``beta`` and
    ``g`` are 0 from there on, so the state neither decays nor is written;
    ``o`` at those positions is then not the model's and must not be used).
    Inside a chunk of ``GDN_CHUNK`` tokens the writes depend on each
    other through the unit lower-triangular system
    ``(I + tril(diag(beta) (K K^T * decay), -1)) U = diag(beta) (V - decayed
    reads of the carried state)``; across chunks the state is carried in
    float32. Starts from the zero state. Returns (state after n_state tokens
    [A, H, K, V], o [A, L, H, V] float32)."""
    A, L, H, K = q.shape
    V = v.shape[-1]
    C = GDN_CHUNK
    pad = (-L) % C
    keep = (jnp.arange(L)[None, :, None] < n_state[:, None, None]).astype(jnp.float32)
    g, beta = g * keep, beta * keep
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, g, beta))
    nc = (L + pad) // C
    hi = jax.lax.Precision.HIGHEST
    # [A, nc, H, C, ...]: a chunk's tokens next to the feature axis
    q, k, v = (jnp.swapaxes(t.reshape(A, nc, C, H, -1), 2, 3) for t in (q, k, v))
    g, beta = (jnp.swapaxes(t.reshape(A, nc, C, H), 2, 3) for t in (g, beta))
    g_cum = jnp.cumsum(g, axis=-1)  # [A, nc, H, C], <= 0 and falling
    tril = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(tril, g_cum[..., :, None] - g_cum[..., None, :], -jnp.inf))  # [.., t, s]
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    t_inv = _unit_lower_inverse(jnp.einsum("...tk,...sk->...ts", k_beta, k, precision=hi) * decay)
    w = jnp.einsum("...ts,...sv->...tv", t_inv, v_beta, precision=hi)  # the writes, had the chunk begun at zero
    k_cum = jnp.einsum("...ts,...sk->...tk", t_inv, k_beta * jnp.exp(g_cum)[..., None], precision=hi)
    qk = jnp.einsum("...tk,...sk->...ts", q, k, precision=hi) * decay
    q_in = q * jnp.exp(g_cum)[..., None]  # reads of the carried state
    k_out = k * jnp.exp(g_cum[..., -1:] - g_cum)[..., None]  # writes as the chunk's end sees them
    g_end = jnp.exp(g_cum[..., -1])  # [A, nc, H]

    def chunk(s, xs):  # s [A, H, K, V]
        w_c, k_cum_c, qk_c, q_in_c, k_out_c, g_end_c = xs
        u = w_c - jnp.einsum("ahtk,ahkv->ahtv", k_cum_c, s, precision=hi)
        o = jnp.einsum("ahtk,ahkv->ahtv", q_in_c, s, precision=hi) + jnp.einsum("ahts,ahsv->ahtv", qk_c, u, precision=hi)
        s = s * g_end_c[..., None, None] + jnp.einsum("ahtk,ahtv->ahkv", k_out_c, u, precision=hi)
        return s, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (w, k_cum, qk, q_in, k_out, g_end))
    s_fin, o = jax.lax.scan(chunk, jnp.zeros((A, H, K, V), jnp.float32), xs)
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(A, L + pad, H, V)[:, :L]
    return s_fin.astype(state_dtype), o


def gdn_decode(cfg: HybridConfig, layer: dict, h, state: dict, j, active, live=None):
    """Mixer for one token a slot. h [S, D]; ``state`` holds every
    delta-rule layer's slot state, ``gdn`` [n, S, H / p, K, p * V] (p heads a
    tile: ``cfg.gdn_head_pack``) and ``conv`` [n, S, (taps-1) *
    gdn_conv_dim] (the raw conv inputs [q | k | v] of the last taps-1 tokens,
    oldest first), of which this is layer ``j``. Returns (out [S, D], the
    state with layer j advanced); rows that are not ``active`` keep theirs.

    ``live`` = ``paged_attention_q8.live_order(active)`` runs the recurrence
    in the Pallas kernel (ops/gdn_state_update.py), which reads and writes
    the live slots' state only and in place; without it ``gdn_decode_step``
    passes over all slots under a mask (off a TPU, and the form the tests
    hold the kernel to)."""
    from areal_tpu.ops import gdn_state_update as gsu

    p = cfg.gdn_head_pack
    conv = jax.lax.dynamic_index_in_dim(state["conv"], j, 0, keepdims=False)
    raw, z, g, beta = _gdn_in(cfg, layer, h)
    with jax.named_scope("gdn_conv"):
        acc, new_conv = _conv_window_step(conv, raw, _gdn_taps(layer), None, active)
        q, k, v = _gdn_heads(cfg, jax.nn.silu(acc))
    with jax.named_scope("gdn_state"):
        if live is None:
            old = jax.lax.dynamic_index_in_dim(state["gdn"], j, 0, keepdims=False)
            new, o = gdn_decode_step(gsu.unpack_state(old, p), q, k, v, jnp.exp(g), beta, active)
            new = gsu.pack_state(new, p)
        else:
            gdn_all, o = gsu.gdn_state_update_stacked(state["gdn"], j, q, k, v, jnp.exp(g), beta, *live)
        y = _gdn_out(cfg, layer, o, z, h.dtype)
    with jax.named_scope("state_write"):
        if live is None:
            gdn_all = jax.lax.dynamic_update_index_in_dim(state["gdn"], new, j, 0)
        state = {"gdn": gdn_all, "conv": jax.lax.dynamic_update_index_in_dim(state["conv"], new_conv, j, 0)}
    with jax.named_scope("gdn_proj"):
        return _proj(cfg, layer, "o_proj", y), state


def gdn_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtypes):
    """Mixer over whole prompts. h [A, L, D], n_state [A]. Returns (out
    [A, L, D], the packed delta-rule state after n_state tokens, the conv
    window of the last taps-1 of those tokens; positions before the prompt
    count as zeros). Between the projections the rows go one at a time: the
    float32 q, k, v and the scan's chunk matrices of one row are a few
    hundred MB at 1,024 tokens, and a batch of 8 has no room for 8 of them
    beside the weights."""
    from areal_tpu.ops.gdn_state_update import pack_state

    L = h.shape[1]
    taps = cfg.gdn_d_conv
    raw, z, g, beta = _gdn_in(cfg, layer, h)
    w = _gdn_taps(layer)

    def row(args):
        raw_r, z_r, g_r, beta_r, n_r = (a[None] for a in args)
        with jax.named_scope("gdn_conv"):
            raw_r = raw_r.astype(state_dtypes[1])  # both forms convolve the values a slot's window would hold
            padded = jnp.pad(raw_r, ((0, 0), (taps - 1, 0), (0, 0)))  # position t at row t + taps - 1
            acc = 0.0
            for i in range(taps):
                acc = acc + padded[:, i : i + L].astype(jnp.float32) * w[i]
            q, k, v = _gdn_heads(cfg, jax.nn.silu(acc))
            conv = _window_after(padded, n_r, taps)
        with jax.named_scope("gdn_state"):
            s, o = gdn_chunked_scan(q, k, v, g_r, beta_r, n_r, state_dtypes[0])
            y = _gdn_out(cfg, layer, o, z_r, h.dtype)
        return y[0], pack_state(s[0], cfg.gdn_head_pack), conv[0].astype(state_dtypes[1])

    y, s, conv = jax.lax.map(row, (raw, z, g, beta, n_state))
    with jax.named_scope("gdn_proj"):
        return _proj(cfg, layer, "o_proj", y), s, conv


# ---------------------------------------------------------------------------
# the delta rule with a decay of its own every key channel (``kda``)
# ---------------------------------------------------------------------------


def _kda_in(cfg: HybridConfig, layer: dict, h):
    """The projections of h [..., D]: (the convs' input [q | k | v] [...,
    kda_conv_dim], the output gate's logits [..., H * V], the log decay a
    [..., H, K] <= 0 and the write strength beta [..., H], both float32).
    ``a = -exp(A_log) softplus(W_fb W_fa h + dt_bias)``, ``beta = sigmoid(W_b
    h)`` (x 2 with ``kda_neg_eigval``)."""
    H, K = cfg.kda_n_heads, cfg.kda_k_dim
    with jax.named_scope("kda_proj"):
        raw = jnp.concatenate([_proj(cfg, layer, n, h) for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
        z = _proj(cfg, layer, "g_b", _proj(cfg, layer, "g_a", h))
        f = _proj(cfg, layer, "f_b", _proj(cfg, layer, "f_a", h)).astype(jnp.float32)
        b = _proj(cfg, layer, "b_proj", h).astype(jnp.float32)
        a = jax.nn.softplus(f + layer["dt_bias"].astype(jnp.float32)).reshape(*h.shape[:-1], H, K)
        a = -jnp.exp(layer["A_log"].astype(jnp.float32))[:, None] * a
        beta = jax.nn.sigmoid(b) * (2.0 if cfg.kda_neg_eigval else 1.0)
    return raw, z, a, beta


def _kda_heads(cfg: HybridConfig, qkv):
    """[..., kda_conv_dim] float32 after conv and SiLU -> q and k [..., H, K]
    L2-normalised (q also scaled by K^-1/2), v [..., H, V]."""
    return _delta_heads(qkv, cfg.kda_n_heads, cfg.kda_k_dim, cfg.kda_v_dim)


def _kda_out(cfg: HybridConfig, layer: dict, o, z, dtype):
    """rmsnorm over each head's values, times sigmoid(gate): [..., H * V]."""
    lead = o.shape[:-2]
    z = z.astype(jnp.float32).reshape(*lead, cfg.kda_n_heads, cfg.kda_v_dim)
    y = _rms_norm(o, layer["o_norm"].astype(jnp.float32), cfg.rms_norm_eps) * jax.nn.sigmoid(z)
    return y.reshape(*lead, -1).astype(dtype)


def kda_decode_step(state, q, k, v, decay, beta, active):
    """The recurrence, one token for each of S slots: ``S' = diag(decay) S``,
    ``w = beta (v - S'^T k)``, ``S = S' + k w^T``, ``o = S^T q``.

    state [S, H, K, V] (its own dtype, computed in float32), q, k and decay =
    exp(a) [S, H, K], v [S, H, V], beta [S, H]. Returns (new state, o [S, H,
    V] float32). A slot that is not ``active`` keeps its state bit for bit."""
    decayed = state.astype(jnp.float32) * decay[..., :, None]
    w = beta[..., None] * (v - jnp.sum(decayed * k[..., :, None], axis=-2))
    new = decayed + k[..., :, None] * w[..., None, :]
    o = jnp.sum(new * q[..., :, None], axis=-2)
    return jnp.where(active[:, None, None, None], new.astype(state.dtype), state), o


def kda_chunked_scan(q, k, v, a, beta, n_state, s0=None):
    """The chunked (WY) algorithm for the same recurrence over ONE prompt, or
    a block of one, from the state ``s0`` [H, K, V] float32 (default zero).

    q, k and a [L, H, K], v [L, H, V], beta [L, H], n_state a scalar: only
    the first ``n_state`` tokens enter the state (``beta`` and ``a`` are 0
    from there on: the state neither decays nor is written; ``o`` there is
    not the model's). With ``G_t`` the running sum of ``a`` inside a chunk
    of ``KDA_CHUNK`` tokens the writes solve the unit lower-triangular system
    ``(I + tril(diag(beta) A, -1)) W = diag(beta) (V - (K * exp(G)) S_0)``,
    ``A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])``, and the reads are
    ``o_t = S_0^T (q_t * exp(G_t)) + sum_{s <= t} B[t, s] w_s`` with ``B`` as
    ``A`` with ``q_t`` for ``k_t``. The decay cannot be factored out of ``K
    K^T`` as one matrix a head, and ``k exp(G)`` against ``k exp(-G)``
    overflows float32 once a channel's log decay over a chunk passes -88. So
    EVERY exponent taken here is <= 0, as ``fla``'s kernels arrange it: a
    chunk is 4 sub-blocks of ``KDA_SUB`` = 16 tokens; a query in sub-block i
    meets the keys of EARLIER sub-blocks through the sub-block's first token
    r_i, ``(k_t exp(G_t - G_r))`` against ``(k_s exp(G_r - G_s))``, both
    exponents differences in the direction of time; keys of its OWN sub-block
    it meets directly, ``exp(G_t - G_s)`` for s <= t, one key offset at a
    time. It therefore holds to NO bound on ``|a_t|``: a product that
    underflows is a term whose true value is below float32's smallest too.
    Across chunks the state is carried in float32. Returns (state after
    min(n_state, L) tokens [H, K, V] float32, o [L, H, V] float32)."""
    L, H, K = q.shape
    V = v.shape[-1]
    C, B = KDA_CHUNK, KDA_SUB
    nb = C // B
    pad = (-L) % C
    keep = (jnp.arange(L) < n_state).astype(jnp.float32)
    a, beta = a * keep[:, None, None], beta * keep[:, None]
    if pad:
        q, k, v, a, beta = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) for t in (q, k, v, a, beta))
    nc = (L + pad) // C
    hi = jax.lax.Precision.HIGHEST
    # [nc, H, C, .]: a chunk's tokens next to the feature axis
    q, k, v, a = (jnp.swapaxes(t.reshape(nc, C, H, -1), 1, 2) for t in (q, k, v, a))
    beta = jnp.swapaxes(beta.reshape(nc, C, H), 1, 2)
    g = jnp.cumsum(a, axis=2)  # [nc, H, C, K], <= 0 and falling along C
    sub = lambda t: t.reshape(nc, H, nb, B, K)  # noqa: E731  [nc, H, sub-block, token, K]
    g_s, q_s, k_s = sub(g), sub(q), sub(k)
    ref = g_s[:, :, :, :1, :]  # G at each sub-block's first token
    own = jnp.exp(g_s - ref)  # a token against its sub-block's first: exponent <= 0
    # the keys of EARLIER sub-blocks as sub-block i sees them, [nc, H, i, C, K]: zero from sub-block i on
    earlier = (jnp.arange(C)[None, :] // B < jnp.arange(nb)[:, None])[None, None, :, :, None]
    k_seen = k[:, :, None] * jnp.exp(jnp.where(earlier, ref - g[:, :, None], -jnp.inf))
    a_mat = jnp.einsum("nhitk,nhisk->nhits", k_s * own, k_seen, precision=hi).reshape(nc, H, C, C)
    b_mat = jnp.einsum("nhitk,nhisk->nhits", q_s * own, k_seen, precision=hi).reshape(nc, H, C, C)
    # a sub-block against itself, one key offset j at a time: exp(G_t - G_j) for t >= j
    t_at = jnp.arange(B)
    a_own, b_own = [], []
    for j in range(B):
        e = jnp.exp(jnp.where((t_at >= j)[:, None], g_s - g_s[:, :, :, j : j + 1, :], -jnp.inf)) * k_s[:, :, :, j : j + 1, :]
        a_own.append(jnp.sum(k_s * e, axis=-1))
        b_own.append(jnp.sum(q_s * e, axis=-1))
    diag = jnp.eye(nb, dtype=jnp.float32)[:, None, :, None]  # [i, t, i', s]: a sub-block's own keys

    def placed(cols):  # [B (s)] of [nc, H, nb, B (t)] -> [nc, H, C, C], zero off the diagonal sub-blocks
        m = jnp.stack(cols, axis=-1)  # [nc, H, nb, t, s]
        return (m[:, :, :, :, None, :] * diag).reshape(nc, H, C, C)

    a_mat, b_mat = a_mat + placed(a_own), b_mat + placed(b_own)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    t_inv = _unit_lower_inverse(a_mat * beta[..., None])
    w0 = jnp.einsum("nhts,nhsv->nhtv", t_inv, v_beta, precision=hi)  # the writes, had the chunk begun at zero
    k_cum = jnp.einsum("nhts,nhsk->nhtk", t_inv, k_beta * jnp.exp(g), precision=hi)
    q_in = q * jnp.exp(g)  # reads of the carried state
    k_out = k * jnp.exp(g[:, :, -1:, :] - g)  # writes as the chunk's end sees them
    g_end = jnp.exp(g[:, :, -1, :])  # [nc, H, K]

    def chunk(s, xs):  # s [H, K, V]
        w0_c, k_cum_c, b_c, q_in_c, k_out_c, g_end_c = xs
        w = w0_c - jnp.einsum("htk,hkv->htv", k_cum_c, s, precision=hi)
        o = jnp.einsum("htk,hkv->htv", q_in_c, s, precision=hi) + jnp.einsum("hts,hsv->htv", b_c, w, precision=hi)
        s = s * g_end_c[..., None] + jnp.einsum("htk,htv->hkv", k_out_c, w, precision=hi)
        return s, o

    s0 = jnp.zeros((H, K, V), jnp.float32) if s0 is None else s0.astype(jnp.float32)
    s_fin, o = jax.lax.scan(chunk, s0, (w0, k_cum, b_mat, q_in, k_out, g_end))
    o = jnp.swapaxes(o, 1, 2).reshape(L + pad, H, V)[:L]
    return s_fin, o


def kda_takes_launch(cfg: HybridConfig) -> bool:
    """Whether a decode step's recurrence runs under ``ops/kda_state_update``
    where the step runs its kernels, from the shapes alone: a head's state
    [K, V] whole float32 tiles (128 x 128 as published)."""
    return cfg.kda_v_dim % 128 == 0 and cfg.kda_k_dim % 8 == 0


def kda_decode(cfg: HybridConfig, layer: dict, h, state: dict, j, active, live=None):
    """Mixer for one token a slot. h [S, D]; ``state`` holds every ``kda``
    layer's slot state, ``kda`` [n, S, H, K, V] and ``conv`` [n, S, (taps-1)
    * kda_conv_dim] (the raw conv inputs [q | k | v] of the last taps-1
    tokens, oldest first), of which this is layer ``j``. Returns (out [S,
    D], the state with layer j advanced); rows that are not ``active`` keep
    theirs.

    ``live`` = ``paged_attention_q8.live_order(active)`` runs the recurrence
    in the Pallas kernel (ops/kda_state_update.py), which reads and writes
    the live slots' state only and in place; without it ``kda_decode_step``
    passes over all slots under a mask (off a TPU, and the form the tests
    hold the kernel to)."""
    conv = jax.lax.dynamic_index_in_dim(state["conv"], j, 0, keepdims=False)
    raw, z, a, beta = _kda_in(cfg, layer, h)
    with jax.named_scope("kda_conv"):
        acc, new_conv = _conv_window_step(conv, raw, _gdn_taps(layer), None, active)
        q, k, v = _kda_heads(cfg, jax.nn.silu(acc))
    with jax.named_scope("kda_state"):
        if live is None:
            old = jax.lax.dynamic_index_in_dim(state["kda"], j, 0, keepdims=False)
            new, o = kda_decode_step(old, q, k, v, jnp.exp(a), beta, active)
        else:
            from areal_tpu.ops.kda_state_update import kda_state_update_stacked

            kda_all, o = kda_state_update_stacked(state["kda"], j, q, k, v, jnp.exp(a), beta, *live)
        y = _kda_out(cfg, layer, o, z, h.dtype)
    with jax.named_scope("state_write"):
        if live is None:
            kda_all = jax.lax.dynamic_update_index_in_dim(state["kda"], new, j, 0)
        state = {"kda": kda_all, "conv": jax.lax.dynamic_update_index_in_dim(state["conv"], new_conv, j, 0)}
    with jax.named_scope("kda_proj"):
        return _proj(cfg, layer, "o_proj", y), state


def kda_prefill_launch(cfg: HybridConfig, L: int) -> bool:
    """Whether the prompt pass of bucket ``L`` scans its ``kda`` layers'
    recurrence under ``ops/kda_prompt_scan`` on a TPU, from the shapes alone
    (``prefill_takes_launch``'s way): a head's keys and values whole lane
    tiles (128 x 128 as published), so that a ``(chunk, head)`` block of the
    projections' own [L, H * K] layout is a head's chunk; ``kda_prefill``'s
    blocks are whole chunks whatever ``L`` is. Elsewhere ``kda_chunked_scan``
    stays: the CPU path, and the launch's oracle. What the engine counts
    ``areal_decode_prefill_kda_launch_tokens_total`` by."""
    return bool(cfg.count("kda")) and jax.default_backend() == "tpu" and cfg.kda_k_dim % 128 == 0 and cfg.kda_v_dim % 128 == 0


def kda_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtypes):
    """Mixer over whole prompts. h [A, L, D], n_state [A]. Returns (out [A,
    L, D], the state after n_state tokens [A, H, K, V], the conv window of
    the last taps-1 of those tokens; positions before the prompt count as
    zeros). The rows go one at a time, and a row in BLOCKS of up to
    ``_KDA_BLOCK_TOKENS`` tokens with the float32 state carried between
    them: a block's projections, convs (over the block and the taps-1 tokens
    before it, whose projections are made again), gates, scan and output
    gate are all its own, so that a 16k-token prompt holds the float32 q, k,
    v, log decay and chunk matrices of 1,024 tokens (0.4 GB at 64 heads of
    128) and never of the prompt (6 GB). Where ``kda_prefill_launch`` says so
    a block's scan is ONE launch of ``kda_prompt_scan``, which keeps the
    chunk matrices and the state in VMEM."""
    A, L, D = h.shape
    taps = cfg.kda_d_conv
    C = KDA_CHUNK
    Lp = -(-L // C) * C
    blk = C * _pow2_part(Lp // C, _KDA_BLOCK_TOKENS // C)
    w = _gdn_taps(layer)
    H, K, V = cfg.kda_n_heads, cfg.kda_k_dim, cfg.kda_v_dim
    scan = kda_chunked_scan
    if kda_prefill_launch(cfg, L):
        from areal_tpu.ops.kda_prompt_scan import kda_prompt_scan as scan

    def row(args):
        h_r, n_r = args  # [L, D], scalar
        # position t at row t + taps - 1: zeros before the prompt (no bias anywhere: their projections are zeros too)
        padded = jnp.pad(h_r, ((taps - 1, Lp - L), (0, 0)))

        def block(s, i):
            hb = jax.lax.dynamic_slice_in_dim(padded, i * blk, blk + taps - 1, axis=0)
            raw, z, a, beta = _kda_in(cfg, layer, hb)
            with jax.named_scope("kda_conv"):
                raw = raw.astype(state_dtypes[1])  # both forms convolve the values a slot's window would hold
                acc = 0.0
                for t in range(taps):
                    acc = acc + raw[t : t + blk].astype(jnp.float32) * w[t]
                q, k, v = _kda_heads(cfg, jax.nn.silu(acc))
            with jax.named_scope("kda_state"):
                own = slice(taps - 1, None)  # the block's own tokens
                s, o = scan(q, k, v, a[own], beta[own], n_r - i * blk, s)
                y = _kda_out(cfg, layer, o, z[own], h.dtype)
            with jax.named_scope("kda_proj"):
                return s, _proj(cfg, layer, "o_proj", y)

        s, out = jax.lax.scan(block, jnp.zeros((H, K, V), jnp.float32), jnp.arange(Lp // blk, dtype=jnp.int32))
        with jax.named_scope("kda_proj"):
            # the window a slot keeps: the raw conv inputs of tokens n_r - taps + 1 .. n_r - 1 (padded rows n_r .. n_r +
            # taps - 2), projected again: three rows
            last = jax.lax.dynamic_slice_in_dim(padded, n_r, taps - 1, axis=0)
            conv = jnp.concatenate([_proj(cfg, layer, n, last) for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
        return out.reshape(Lp, D)[:L], s.astype(state_dtypes[0]), conv.reshape(-1).astype(state_dtypes[1])

    return jax.lax.map(row, (h, n_state))


# ---------------------------------------------------------------------------
# the latent-attention mixer
# ---------------------------------------------------------------------------


def _pairs_to_halves(x):
    """(even, odd) pairs along the last axis -> [evens | odds]: the published
    checkpoints keep the rotary part interleaved, ``_rope`` rotates halves."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _rope_latent(cfg: HybridConfig, x, positions):
    """The latent-attention mixer's rotary embedding of x [..., heads, rope] (halves) at ``positions`` [...]: every
    pair by ``rope_theta`` alone, or, where the configuration scales it (``rope_yarn``), pair i by YaRN's table
    ``rope_inv_freq[i]``, cos and sin unscaled: YaRN's factor stands on the softmax scale (``sm_scale``). A key goes
    into its page rotated by the same table, so a read takes the page as it lies."""
    if cfg.rope_yarn is None:
        return _rope(x, positions, cfg.rope_theta)
    half = x.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(cfg.rope_inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _mla_in(cfg: HybridConfig, layer: dict, h, positions, query: bool = True):
    """The projections of h [..., D] at ``positions`` [...]: (q_nope
    [..., H, nope], q_rope [..., H, rope] rotated, the normed latent c
    [..., rank], the ONE rotary key k_r [..., rope] rotated, the normed
    low-rank query q_r [..., q_lora_rank] or None where the query is one
    full-rank matrix). ``[c | k_r]`` is what the token leaves behind.
    ``query`` False leaves q_nope and q_rope None where there is a q_r to
    make them from later (``mla_query``: a long prompt's, a block at a time)."""
    r = cfg.kv_lora_rank
    # each projection is ONE matmul whose output is split afterwards: left to
    # itself XLA:TPU pushes the splits into the weights, wants those in
    # another layout and copies the whole layer stack of them, once a program
    # (1.2 GB for W_q at 47 layers; tests/test_tpu_compile.py)
    if cfg.q_lora_rank:
        with jax.named_scope("mla_q_lora"):
            qa, kva = jax.lax.optimization_barrier((_proj(cfg, layer, "w_qa", h), _proj(cfg, layer, "w_kva", h)))
            q_r = _rms_norm(qa, layer["q_a_norm"], cfg.rms_norm_eps)
        q_nope, q_rope = mla_query(cfg, layer, q_r, positions) if query else (None, None)
    else:
        with jax.named_scope("mla_proj"):
            q, kva = jax.lax.optimization_barrier((_proj(cfg, layer, "wq", h), _proj(cfg, layer, "w_kva", h)))
        q_r = None
        q_nope, q_rope = _split_query(cfg, q, positions)
    with jax.named_scope("mla_proj"):
        c = _rms_norm(kva[..., :r], layer["kv_norm"], cfg.rms_norm_eps)
        k_r = kva[..., r:][..., None, :]  # one "head"
        if cfg.rope_interleave:
            k_r = _pairs_to_halves(k_r)
        k_r = _rope_latent(cfg, k_r, positions)[..., 0, :]
    return q_nope, q_rope, c, k_r, q_r


def _split_query(cfg: HybridConfig, q, positions):
    """q [..., H * (nope + rope)] -> (q_nope [..., H, nope], q_rope [..., H, rope] rotated)."""
    with jax.named_scope("mla_proj"):
        dn = cfg.qk_nope_head_dim
        q = q.reshape(*q.shape[:-1], cfg.num_heads, dn + cfg.qk_rope_head_dim)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        if cfg.rope_interleave:
            q_rope = _pairs_to_halves(q_rope)
        return q_nope, _rope_latent(cfg, q_rope, positions)


def mla_query(cfg: HybridConfig, layer: dict, q_r, positions):
    """The heads' queries from the normed low-rank query q_r [..., q_lora_rank]."""
    with jax.named_scope("mla_q_lora"):
        q = jax.lax.optimization_barrier(_proj(cfg, layer, "w_qb", q_r))  # ONE matmul, split afterwards: see _mla_in
    return _split_query(cfg, q, positions)


def _rope_head(cfg: HybridConfig, x, positions):
    """The index's rotary embedding: the FIRST ``qk_rope_head_dim`` values of
    x [..., heads, index_head_dim] turn, the rest pass."""
    dr = cfg.qk_rope_head_dim
    turn = _pairs_to_halves(x[..., :dr]) if cfg.index_rope_interleave else x[..., :dr]
    return jnp.concatenate([_rope_latent(cfg, turn, positions), x[..., dr:]], axis=-1)


def index_key(cfg: HybridConfig, layer: dict, h, positions):
    """The ONE index key a token leaves behind, [..., index_head_dim]: a
    LayerNorm (weight and bias) over ``W^I_k h``, its rotary part turned."""
    with jax.named_scope("dsa_index_proj"):
        k = _proj(cfg, layer, "wi_k", h).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True) + cfg.index_norm_eps)
        k = (k * layer["wi_k_norm"] + layer["wi_k_norm_bias"]).astype(h.dtype)
        return _rope_head(cfg, k[..., None, :], positions)[..., 0, :]


def index_key_view(cfg: HybridConfig, rows):
    """Cached index keys [..., index_head_dim] in the PUBLISHED order of
    values: a page holds the rotary part as [evens | odds] halves
    (``_pairs_to_halves``, as queries meet it), the published code as (even,
    odd) pairs."""
    dr = cfg.qk_rope_head_dim
    if not cfg.index_rope_interleave:
        return rows
    pairs = jnp.stack([rows[..., : dr // 2], rows[..., dr // 2 : dr]], axis=-1).reshape(*rows.shape[:-1], dr)
    return jnp.concatenate([pairs, rows[..., dr:]], axis=-1)


def index_query(cfg: HybridConfig, layer: dict, h, q_r, positions):
    """(the index's queries [..., Hi, index_head_dim] from the normed low-rank
    query q_r, rotated as the key is; the heads' weights [..., Hi] float32
    from the layer's input). The published ``Hi^-1/2 x index_head_dim^-1/2``
    is a positive constant on every score and moves no selection: left out."""
    with jax.named_scope("dsa_index_proj"):
        q = jax.lax.optimization_barrier(_proj(cfg, layer, "wi_qb", q_r))  # ONE matmul, split afterwards: see _mla_in
        q = q.reshape(*q_r.shape[:-1], cfg.index_n_heads, cfg.index_head_dim)
        return _rope_head(cfg, q, positions), _proj(cfg, layer, "wi_w", h).astype(jnp.float32)


def index_scores(q_i, w, k_i):
    """I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s]) float32: q_i [T, Hi,
    d], w [T, Hi], k_i [S, d] -> [T, S]; with a leading batch axis on k_i (a
    slot's own keys, [T, S, d]) each query meets its own."""
    keys = "tsd" if k_i.ndim == 3 else "sd"
    dots = jnp.einsum(f"thd,{keys}->ths", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.einsum("ths,th->ts", jax.nn.relu(dots), w)


def select_top(scores, valid, topk: int):
    """bool [T, S]: of each row's ``valid`` positions the min(topk, how many
    are valid) of largest ``scores`` (float32), EXACTLY: between equal scores
    the lower position wins, as ``jax.lax.top_k`` orders them. No sort: the
    k-th largest score of a row is found bit by bit (32 counting passes over
    the scores' order-preserving integers), then everything above it is in
    and of its equals the first few."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # order-preserving: flip a negative float's magnitude bits, then the sign bit of all
    key = jax.lax.bitcast_convert_type(bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32) ^ jnp.uint32(0x80000000)
    key = jnp.where(valid, key, jnp.uint32(0))
    k = jnp.minimum(topk, jnp.sum(valid, axis=-1, dtype=jnp.int32))

    def bit(i, t):
        cand = t | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        return jnp.where(jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32) >= k, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:1], jnp.uint32))[:, None]
    above, equal = key > kth, (key == kth) & valid
    left = (k - jnp.sum(above, axis=-1, dtype=jnp.int32))[:, None]
    # all of the k-th score's equals fit (float32 scores: nearly always the k-th alone), or the first few of
    # them do: the running count over a row is a pass of its own, made only where some row needs it
    chosen = jax.lax.cond(
        jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32)[:, None] > left),
        lambda: above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= left)),
        lambda: above | equal,
    )
    return chosen & valid


def index_select(cfg: HybridConfig, q_i, w_i, idx_pages, j, lengths, page_table, page_size: int, kernel: dict | None = None):
    """What a decode step's queries select: bool [S, window] over each
    slot's cached tokens (``lengths`` [S] of them, this step's own among
    them), the min(index_topk, cached) of largest index score, exactly.
    Every cached token of the slot is scored by its ONE index key in layer
    ``j`` of the index pool ``idx_pages`` [layers, 1, N, psz, d]: with
    ``kernel`` (the launch's block and its work list for these lengths) by
    ``ops/paged_latent_attention.py paged_index_scores_stacked`` over the
    pages that hold tokens, else over the gathered window."""
    S, window = page_table.shape[0], page_table.shape[1] * page_size
    with jax.named_scope("dsa_index_score"):
        if kernel is not None:
            from areal_tpu.ops.paged_latent_attention import paged_index_scores_stacked

            scores = paged_index_scores_stacked(q_i, w_i, idx_pages, j, lengths, page_table, **kernel)
        else:
            keys = jax.lax.dynamic_index_in_dim(idx_pages, j, 0, keepdims=False)[0][page_table]  # [S, wp, psz, d]
            scores = index_scores(q_i, w_i, keys.reshape(S, window, keys.shape[-1]))
    with jax.named_scope("dsa_select"):
        cached = jnp.arange(window, dtype=jnp.int32)[None, :] < lengths[:, None]
        return select_top(scores, cached, cfg.index_topk)


def _latent_row(cfg: HybridConfig, c, k_r):
    """[c | k_r | 0] as a page stores it: [..., 1, latent_lanes]."""
    row = jnp.concatenate([c, k_r], axis=-1)
    pad = cfg.latent_lanes - cfg.latent_dim
    row = jnp.pad(row, ((0, 0),) * (row.ndim - 1) + ((0, pad),)) if pad else row
    return row[..., None, :]


def _w_kvb_heads(cfg: HybridConfig, layer: dict):
    """W_kvb as (W_UK [rank, H, nope], W_UV [rank, H, v]): slices, never a second copy kept."""
    w = layer["w_kvb"].reshape(cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


# float32 logits a block of queries may hold against a block of keys, every head at once (the XLA form)
_PREFILL_LOGIT_BYTES = 128 << 20
_PREFILL_KEY_BLOCK = 2048
# under the launch (ops/latent_prefill_attention.py) the logits live in VMEM, and what holds a block of queries
# is the index's float32 scores against every key of the prompt, [queries, L], and the selection's passes over them
_PREFILL_SCORE_BYTES = 32 << 20
_PREFILL_LAUNCH_BLOCKS = (1024, 1024)
_PREFILL_LAUNCH_TOKENS = 1024  # the shortest prompt that takes the launch
# float32 [queries, index heads, keys] dots of the index a block of queries may hold against a block of keys
_PREFILL_INDEX_DOT_BYTES = 64 << 20


def prefill_takes_launch(cfg: HybridConfig, L: int) -> bool:
    """Whether a prompt of ``L`` tokens (its program's bucket) attends under
    ``mla_prefill_flash`` on a TPU, from the shapes alone: a prompt of 1,024
    tokens or more whose blocks are whole lane tiles (a bucket is a multiple
    of 256; a head's values whole tiles). Under 1,024 the XLA loop is one
    block whose logits (75 MB at 32 heads x 768) still pass at XLA's speed,
    and a launch's steps of one small block a head cost more than they save:
    the probe reads 334 / 340 / 503 us a layer against XLA's 265 / 240 / 341
    at 32 heads x 256 / 512 / 768, and 413 against 1,215 at 1,024 (PERF.md,
    PR 40)."""
    return L >= _PREFILL_LAUNCH_TOKENS and L % 128 == 0 and cfg.v_head_dim % 128 == 0


def prefill_attn_launch(cfg: HybridConfig, L: int) -> bool:
    """Whether the prefill program of bucket ``L`` runs its latent-attention
    layers' attention under the launch: what ``mla_prefill_attend`` goes by,
    and what the engine counts
    ``areal_decode_prefill_attn_launch_tokens_total`` by."""
    return "mla" in cfg.layer_types and jax.default_backend() == "tpu" and prefill_takes_launch(cfg, L)


def _pow2_part(L: int, fit: int) -> int:
    """The largest power of two up to ``fit`` that divides ``L``."""
    return math.gcd(L, 1 << (max(1, fit).bit_length() - 1))


def prefill_blocks(cfg: HybridConfig, L: int, launch: bool = False) -> tuple[int, int]:
    """(queries, keys) a block of the prompt pass's attention, from the
    shapes alone. The XLA form: keys in blocks of up to 2,048 that divide
    the prompt, queries in as many as keep [H, queries, keys] float32 logits
    inside 128 MB (32 heads x 1,024 x 1,024: a short prompt is one block; 64
    heads against 2,048 keys: 256 queries). Under the launch the logits
    never leave VMEM: blocks of up to 1,024 x 1,024 that divide the prompt
    (the probe's fastest at 32 heads and within 4% of it at 64; a head's key
    and value block is then fetched for 512-1,024 operations a byte, two to
    four times the chip's ridge), the queries also held to 32 MB of the
    index's float32 [queries, L] scores where the layer has an index: 1,024
    up to 8k tokens, 512 at 12k and 16k, where 1,024 would put the 16k
    program's temporaries 25 MB over the XLA form's (PERF.md, PR 40)."""
    if launch:
        tq, tk = _PREFILL_LAUNCH_BLOCKS
        if cfg.index_topk:
            tq = min(tq, max(32, _PREFILL_SCORE_BYTES // (4 * L)))
        return _pow2_part(L, tq), _pow2_part(L, tk)
    tk = math.gcd(L, _PREFILL_KEY_BLOCK)
    return _pow2_part(L, max(8, _PREFILL_LOGIT_BYTES // (4 * cfg.num_heads * tk))), tk


def prefill_attend_block(cfg: HybridConfig, qn, qr, kv, k_r, i, blocks: tuple[int, int], chosen=None, launch: bool = False):
    """Block ``i`` of ``blocks[0]`` queries (qn [tq, H, nope], qr [tq, H,
    rope]) over the key blocks of ``blocks[1]`` up to its diagonal, under a
    running softmax: [tq, H * v]. ``chosen`` bool [tq, L]: the keys each
    query attends to (else every key up to the query). The XLA loop takes
    ``kv`` [L, H, nope + v] and ``k_r`` [L, rope]; with ``launch`` the walk
    is ONE Pallas launch (``ops/latent_prefill_attention.py``) over ``kv``
    [L, H * (key lanes + v)] as ``padded_w_kvb`` lays it out."""
    tq, tk = blocks
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    if launch:
        from areal_tpu.ops import latent_prefill_attention as flash

        with jax.named_scope("attn"):
            q_lanes, kr_lanes = flash.prefill_operands(qn, qr, k_r)
            return flash.mla_prefill_flash(q_lanes, kv, kr_lanes, i, chosen, heads=H, block_k=tk, sm_scale=cfg.sm_scale)
    pos = i * tq + jnp.arange(tq, dtype=jnp.int32)
    k_pos = jnp.arange(tk, dtype=jnp.int32)

    def attend(kb, carry):
        m_prev, l_prev, acc = carry
        with jax.named_scope("attn"):
            kvb = jax.lax.dynamic_slice_in_dim(kv, kb * tk, tk, axis=0)
            krb = jax.lax.dynamic_slice_in_dim(k_r, kb * tk, tk, axis=0)
            logits = jnp.einsum("thd,shd->hts", qn, kvb[..., :dn], preferred_element_type=jnp.float32)
            logits = logits + jnp.einsum("thd,sd->hts", qr, krb, preferred_element_type=jnp.float32)
            if chosen is not None:
                seen = jax.lax.dynamic_slice_in_dim(chosen, kb * tk, tk, axis=1)
            else:
                seen = pos[:, None] >= (kb * tk + k_pos)[None, :]
            logits = jnp.where(seen[None], logits * cfg.sm_scale, -1e30)
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.where(seen[None], jnp.exp(logits - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            pv = jnp.einsum("hts,shd->htd", p.astype(kv.dtype), kvb[..., dn:], preferred_element_type=jnp.float32)
            return m_new, l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), acc * corr + pv

    init = (jnp.full((H, tq, 1), -1e30, jnp.float32), jnp.zeros((H, tq, 1), jnp.float32), jnp.zeros((H, tq, dv), jnp.float32))
    n_kb = ((i + 1) * tq + tk - 1) // tk  # key blocks up to the diagonal
    _, l, acc = jax.lax.fori_loop(0, n_kb, attend, init)
    with jax.named_scope("attn"):
        return jnp.swapaxes(acc / l, 0, 1).reshape(tq, H * dv).astype(kv.dtype)


def mla_prefill_attend(cfg: HybridConfig, layer: dict, q, c, k_r, index=None, launch: bool | None = None):
    """The PLAIN form over ONE prompt, blocked over queries: every head's key
    and value made from the prompt's own latent (``[k_nope | v] = W_kvb c``,
    the rotary key shared by all heads), causal softmax of q k^T / sqrt(nope
    + rope) over the keys each query SELECTS, then ``W_o``. ``q`` is
    (q_nope, q_rope) [L, H, .] or, where the query has a low-rank path, the
    normed q_r [L, q_lora_rank] (a block's heads are then made in the block:
    64 heads of 256 for 16k tokens are 0.5 GB); c [L, rank], k_r [L, rope].
    ``index`` = (h [L, D] the layer's input, k_i [L, index_head_dim]): a
    query block scores every key up to its diagonal, selects
    (``select_top``), and attends under that mask; without it every key up
    to the query. No [H, L, L]: a block of queries meets a block of keys at a
    time with a running softmax, and key blocks past the diagonal are never
    visited (``prefill_attend_block``): ONE Pallas launch a query block,
    the logits in VMEM, on a TPU where the shapes allow
    (``prefill_attn_launch``; ``launch`` says so for a test), else the XLA
    loop, the launch's oracle. Returns [L, D]."""
    L = c.shape[0]
    if launch is None:
        launch = prefill_attn_launch(cfg, L)
    tq, tk = prefill_blocks(cfg, L, launch)
    with jax.named_scope("attn"):
        if launch:  # a head's [k_nope | 0 | v] as a column block: W_kvb's columns laid out so, once a layer
            from areal_tpu.ops.latent_prefill_attention import padded_w_kvb

            kv = c @ padded_w_kvb(layer["w_kvb"], cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim)
        else:
            kv = (c @ layer["w_kvb"]).reshape(L, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)

    def block(i):
        pos = i * tq + jnp.arange(tq, dtype=jnp.int32)
        rows = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=i * tq, slice_size=tq, axis=0)
        if isinstance(q, tuple):
            qn, qr = rows(q[0]), rows(q[1])
        else:
            qn, qr = mla_query(cfg, layer, rows(q), pos)
        chosen = None
        if index is not None:
            h, k_i = index
            q_i, w = index_query(cfg, layer, rows(h), rows(q), pos)
            # keys a scored block: the index's float32 [queries, heads, keys] dots inside their bytes
            ts = _pow2_part(L, max(128, _PREFILL_INDEX_DOT_BYTES // (4 * cfg.index_n_heads * tq)))

            def score(kb, sc):
                with jax.named_scope("dsa_index_score"):
                    blk = index_scores(q_i, w, jax.lax.dynamic_slice_in_dim(k_i, kb * ts, ts, axis=0))
                    return jax.lax.dynamic_update_slice_in_dim(sc, blk, kb * ts, axis=1)

            with jax.named_scope("dsa_index_score"):
                scores = jax.lax.fori_loop(0, ((i + 1) * tq + ts - 1) // ts, score, jnp.zeros((tq, L), jnp.float32))
            with jax.named_scope("dsa_select"):
                chosen = select_top(scores, pos[:, None] >= jnp.arange(L, dtype=jnp.int32)[None, :], cfg.index_topk)
        o = prefill_attend_block(cfg, qn, qr, kv, k_r, i, (tq, tk), chosen, launch)
        with jax.named_scope("mla_proj"):
            return _proj(cfg, layer, "wo", o)

    return jax.lax.map(block, jnp.arange(L // tq, dtype=jnp.int32)).reshape(L, -1)


def mla_absorbed_query(cfg: HybridConfig, layer: dict, q_nope, q_rope):
    """The ABSORBED form's query over latent rows: ``[W_UK^T q_nope | q_rope
    | 0]`` [S, H, latent_lanes], so that its product with a stored row
    ``[c | k_r | 0]`` is the plain form's q . k."""
    w_uk, _ = _w_kvb_heads(cfg, layer)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk, preferred_element_type=jnp.float32).astype(q_nope.dtype)
    return _latent_row(cfg, q_lat, q_rope)[..., 0, :]


def mla_absorbed_out(cfg: HybridConfig, layer: dict, o_lat):
    """o_lat [S, H, rank] (the probabilities' sum of latents) -> [S, H * v]:
    the value up-projection moved across the sum."""
    _, w_uv = _w_kvb_heads(cfg, layer)
    o = jnp.einsum("shr,rhd->shd", o_lat.astype(w_uv.dtype), w_uv, preferred_element_type=jnp.float32)
    return o.reshape(o.shape[0], -1).astype(o_lat.dtype)


# ---------------------------------------------------------------------------
# a decoder-hybrid-decoder's mixers: the selective scan (Mamba-1), the gated
# memory unit, differential attention over a window, every token, or another
# layer's pages
# ---------------------------------------------------------------------------


def _s6_gates(cfg: HybridConfig, layer: dict, c):
    """The input-dependent parts of the scan from c [..., channels] (after
    the conv and SiLU, float32): (step size d [..., channels] after its
    softplus, B and C [..., state size]), float32. ``[r | B | C] = W_x c``,
    ``d = softplus(W_dt r + dt_bias)``."""
    R, N = cfg.s6_dt_rank, cfg.mamba_d_state
    r, b, cc = jnp.split(_proj(cfg, layer, "x_proj", c.astype(cfg.jax_dtype)), [R, R + N], axis=-1)
    d = jax.nn.softplus(_proj(cfg, layer, "dt_proj", r).astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    return d, b.astype(jnp.float32), cc.astype(jnp.float32)


def s6_decode_step(ssm, c, d, b, cc, a, active):
    """The recurrence, one token for each of S slots: ``S = exp(d (x) A) * S
    + (d * c) B^T``, ``y = S C``. ssm [S, N, channels] (its own dtype,
    computed in float32), c and d [S, channels], b and cc [S, N], a = -exp(
    A_log) [N, channels]. Returns (new state, y [S, channels] float32). A
    slot that is not ``active`` keeps its state bit for bit."""
    new = ssm.astype(jnp.float32) * jnp.exp(d[:, None, :] * a[None]) + (d * c)[:, None, :] * b[:, :, None]
    y = jnp.sum(new * cc[:, :, None], axis=1)
    return jnp.where(active[:, None, None], new.astype(ssm.dtype), ssm), y


def s6_scan(c, d, b, cc, a, n_state, state_dtype=jnp.float32, unroll: int = 8):
    """The same recurrence over whole prompts, token by token: c and d [A, L,
    channels], b and cc [A, L, N], n_state [A]: only the first ``n_state``
    tokens of a row enter its state (``d`` is 0 from there on: no decay, no
    input; ``y`` there is not the model's). Starts from the zero state and
    carries [A, N, channels] alone: nothing of [L, channels, N] exists.
    Returns (state after n_state tokens, y [A, L, channels] float32)."""
    A, L, C = c.shape
    d = jnp.where(jnp.arange(L)[None, :, None] < n_state[:, None, None], d, 0.0)

    def token(s, x):
        d_t, c_t, b_t, cc_t = x
        s = s * jnp.exp(d_t[:, None, :] * a[None]) + (d_t * c_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * cc_t[:, :, None], axis=1)

    s0 = jnp.zeros((A, a.shape[0], C), jnp.float32)
    s, y = jax.lax.scan(token, s0, tuple(jnp.swapaxes(t, 0, 1) for t in (d, c, b, cc)), unroll=min(unroll, L))
    return s.astype(state_dtype), jnp.swapaxes(y, 0, 1)


def s6_decode(cfg: HybridConfig, layer: dict, h, state: dict, j, active):
    """Mixer for one token a slot. h [S, D] (normed); ``state`` holds every
    selective-scan layer's slot state, ``ssm`` [n, S, N, channels] and
    ``conv`` [n, S, (K-1) * channels], of which this is layer ``j``. Returns
    (out [S, D], the state with layer j advanced, the scan's output y [S,
    channels] float32 BEFORE its gate: what a memory unit further up reads);
    rows that are not ``active`` keep theirs."""
    conv = jax.lax.dynamic_index_in_dim(state["conv"], j, 0, keepdims=False)
    with jax.named_scope("ssm_proj"):
        u, z = jnp.split(_proj(cfg, layer, "in_proj", h), 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        acc, new_conv = _conv_window_step(conv, u, *_conv_taps(layer), active)
        c = jax.nn.silu(acc)
    with jax.named_scope("ssm_proj"):
        d, b, cc = _s6_gates(cfg, layer, c)
    with jax.named_scope("ssm_state"):
        ssm = jax.lax.dynamic_index_in_dim(state["ssm"], j, 0, keepdims=False)
        ssm, y = s6_decode_step(ssm, c, d, b, cc, -jnp.exp(layer["A_log"].astype(jnp.float32)), active)
        y = y + layer["D"].astype(jnp.float32) * c
        g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
    with jax.named_scope("state_write"):
        state = {
            "ssm": jax.lax.dynamic_update_index_in_dim(state["ssm"], ssm, j, 0),
            "conv": jax.lax.dynamic_update_index_in_dim(state["conv"], new_conv, j, 0),
        }
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), state, y


def s6_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtypes):
    """Mixer over whole prompts. h [A, L, D] (normed), n_state [A]. Returns
    (out [A, L, D], state after n_state tokens, conv window of the last K-1
    of those tokens, the scan's output y [A, L, channels] float32 before its
    gate)."""
    A, L, _ = h.shape
    K = cfg.mamba_d_conv
    with jax.named_scope("ssm_proj"):
        u, z = jnp.split(_proj(cfg, layer, "in_proj", h), 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        w, bias = _conv_taps(layer)
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))  # position t at row t + K - 1
        acc = bias
        for k in range(K):
            acc = acc + padded[:, k : k + L].astype(jnp.float32) * w[k]
        c = jax.nn.silu(acc)
        conv = _window_after(padded, n_state, K)
    with jax.named_scope("ssm_proj"):
        d, b, cc = _s6_gates(cfg, layer, c)
    with jax.named_scope("ssm_state"):
        ssm, y = s6_scan(c, d, b, cc, -jnp.exp(layer["A_log"].astype(jnp.float32)), n_state, state_dtypes[0])
        y = y + layer["D"].astype(jnp.float32) * c
        g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), ssm, conv.astype(state_dtypes[1]), y


def gmu_mix(cfg: HybridConfig, layer: dict, h, m):
    """A gated memory unit: ``W_o (m * silu(W_g h))``, ``m`` the scan output
    (float32, before its gate) of the last selective-scan layer for the SAME
    token. No state of its own."""
    with jax.named_scope("gmu"):
        g = jax.nn.silu(_proj(cfg, layer, "gmu_in", h).astype(jnp.float32))
        return _proj(cfg, layer, "gmu_out", (m * g).astype(h.dtype))


def _diff_proj(cfg: HybridConfig, layer: dict, name: str, h, heads: int, width: int):
    out = _proj(cfg, layer, name, h)
    if cfg.attn_bias:
        out = out + layer[f"{name}_b"]
    return out.reshape(*h.shape[:-1], heads, width)


def _diff_qkv(cfg: HybridConfig, layer: dict, h, query: bool = True, kv: bool = True):
    """q [..., H, hd] (heads 2p and 2p+1 are q1 and q2 of differential head
    p) and k, v [..., KH / 2, 2 hd]: pair r's [k1 | k2] and [v1 | v2], which
    is the projection's own order of heads, two to a row."""
    q = _diff_proj(cfg, layer, "wq", h, cfg.num_heads, cfg.head_dim_) if query else None
    if not kv:
        return q, None, None
    k = _diff_proj(cfg, layer, "wk", h, cfg.kv_pool_heads, cfg.kv_head_dim)
    v = _diff_proj(cfg, layer, "wv", h, cfg.kv_pool_heads, cfg.kv_head_dim)
    return q, k, v


def _diff_pack_q(q):
    """[..., H, hd] -> [..., H, 2 hd]: q1 of a differential head as [q1 | 0]
    and q2 as [0 | q2], so that against a row [k1 | k2] each meets its own
    key, and 4 consecutive query heads read one row: the paged kernels'
    grouped-query shape at 128 lanes."""
    *lead, H, hd = q.shape
    qp = q.reshape(*lead, H // 2, 2, hd)
    zero = jnp.zeros_like(qp[..., 0, :])
    first = jnp.concatenate([qp[..., 0, :], zero], axis=-1)
    second = jnp.concatenate([zero, qp[..., 1, :]], axis=-1)
    return jnp.stack([first, second], axis=-2).reshape(*lead, H, 2 * hd)


def _diff_merge(cfg: HybridConfig, layer: dict, o, depth, dtype):
    """What differential attention does behind the two softmaxes. o [..., H,
    2 hd]: head 2p read pair p // 2's [v1 | v2] under softmax(q1 k1^T), head
    2p+1 under softmax(q2 k2^T). Returns ``(1 - l0) rmsnorm(o1 - lam o2)``
    flat [..., H * hd], ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 =
    0.8 - 0.6 exp(-0.3 depth)``, ``depth`` the layer's index in the model."""
    with jax.named_scope("attn_diff"):
        f32 = jnp.float32
        l0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
        lam = (
            jnp.exp(jnp.sum(layer["lq1"].astype(f32) * layer["lk1"].astype(f32)))
            - jnp.exp(jnp.sum(layer["lq2"].astype(f32) * layer["lk2"].astype(f32)))
            + l0
        )
        *lead, H, wide = o.shape
        o = o.astype(f32).reshape(*lead, H // 2, 2, wide)
        diff = o[..., 0, :] - lam * o[..., 1, :]
        diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        return (diff * layer["sub_norm"].astype(f32) * (1.0 - l0)).reshape(*lead, H // 2 * wide).astype(dtype)


def _depths(cfg: HybridConfig) -> dict[str, jax.Array]:
    """{attending kind: the model indices of its layers, by their index within
    the kind}: what ``_diff_merge`` takes its ``depth`` from inside a scan."""
    return {kind: jnp.asarray(cfg.layers_of(kind) or (0,), jnp.int32) for kind in ("swa", "attention", "cross")}


def _diff_out(cfg: HybridConfig, layer: dict, o):
    out = _proj(cfg, layer, "wo", o)
    return out + layer["wo_b"] if cfg.attn_bias else out


def diff_attend(cfg: HybridConfig, q, k, v, allowed):
    """Both softmaxes of differential attention for one sequence, dense: q
    [T, H, hd], k and v [U, KH / 2, 2 hd], ``allowed`` [T, U] bool. Returns
    [T, H, 2 hd] float32 (``_diff_merge`` takes it from there). A query that
    is allowed no key gets the mean of the values, never a NaN."""
    T, U, hd = q.shape[0], k.shape[0], cfg.head_dim_
    qp = q.reshape(T, cfg.kv_pool_heads, 2, 2, hd)  # [T, pair r, differential head 2r + a, s, hd]
    kp = k.reshape(U, cfg.kv_pool_heads, 2, hd)  # [U, r, s, hd]
    logits = jnp.einsum("trasd,ursd->rastu", qp, kp).astype(jnp.float32) * cfg.sm_scale
    probs = jax.nn.softmax(jnp.where(allowed[None, None, None], logits, -1e30), axis=-1).astype(v.dtype)
    out = jnp.einsum("rastu,ure->trase", probs, v, preferred_element_type=jnp.float32)
    return out.reshape(T, cfg.num_heads, 2 * hd)


def gqa_attend(cfg: HybridConfig, q, k, v, allowed):
    """Plain grouped-query softmax attention for one sequence, dense: q [T,
    H, hd], k and v [U, KH, hd], ``allowed`` [T, U] bool. Returns [T, H *
    hd] in v's type. A query that is allowed no key gets the mean of the
    values, never a NaN."""
    T, G = q.shape[0], cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(T, cfg.num_kv_heads, G, q.shape[-1])
    logits = jnp.einsum("tkgd,ukd->kgtu", qg, k).astype(jnp.float32) * cfg.sm_scale
    probs = jax.nn.softmax(jnp.where(allowed[None, None], logits, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("kgtu,ukd->tkgd", probs, v).reshape(T, -1)


def swa_attend(cfg: HybridConfig, q, k, v):
    """Window attention over whole prompts, O(L x window), the XLA form: a
    query at t attends to keys t - window + 1 .. t. Blocks of ``window``
    queries (of the prompt, where that is shorter) each meet the block before
    and their own, one block at a time: [H, window, 2 window] float32 logits
    a block. Differential attention: q [A, L, H, hd], k and v [A, L, KH / 2,
    2 hd], returns [A, L, H, 2 hd] float32 (``diff_attend``); plain grouped
    queries: k and v [A, L, KH, hd], returns [A, L, H * hd]
    (``gqa_attend``). The CPU path, and the oracle of the launch that serves
    a window whose block of logits does not fit (``swa_prefill_launch``)."""
    A, L = q.shape[:2]
    B = min(cfg.sliding_window, L)
    pad = (-L) % B
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v))
    nb = (L + pad) // B
    attend = diff_attend if cfg.diff_attn else gqa_attend

    def blocks(t):  # [A, L, ...] -> [A * nb, B, ...]
        return t.reshape(A * nb, B, *t.shape[2:])

    def with_before(t):  # a block's keys: the block before (zeros before the first) and its own, [A * nb, 2B, ...]
        tb = t.reshape(A, nb, B, *t.shape[2:])
        before = jnp.concatenate([jnp.zeros_like(tb[:, :1]), tb[:, :-1]], axis=1)
        return jnp.concatenate([before, tb], axis=2).reshape(A * nb, 2 * B, *t.shape[2:])

    t_at, u_at = jnp.arange(B)[:, None], jnp.arange(2 * B)[None, :] - B  # key u of the pair stands at u_at relative to the block's first query
    band = (u_at <= t_at) & (t_at - u_at < cfg.sliding_window)

    def one(args):
        qi, ki, vi, first = args
        return attend(cfg, qi, ki, vi, band & (~first | (u_at >= 0)))

    first = (jnp.arange(A * nb) % nb) == 0
    out = jax.lax.map(one, (blocks(q), with_before(k), with_before(v), first))
    return out.reshape(A, L + pad, *out.shape[2:])[:, :L]


def swa_prefill_launch(cfg: HybridConfig, L: int) -> bool:
    """Whether the prompt pass of bucket ``L`` attends inside its window
    layers' band under ``ops/window_prefill_attention.py`` on a TPU, from the
    shapes alone (``gqa_prefill_launch``'s way): plain grouped queries whose
    XLA block of logits ([H, window, 2 window] float32, the prompt's length
    for the window where that is shorter) passes
    ``_PREFILL_GQA_LOGIT_BYTES`` and whose shape the kernel serves (heads of
    128 lanes unpadded, a row of whole 256-token tiles). Below that
    ``swa_attend`` stays: the CPU path, and the launch's oracle."""
    B = min(cfg.sliding_window, L)
    return (
        bool(cfg.count("swa"))
        and jax.default_backend() == "tpu"
        and not cfg.diff_attn
        and cfg.head_dim_ == cfg.kv_head_dim == 128
        and L % 256 == 0
        and 8 * cfg.num_heads * B * B > _PREFILL_GQA_LOGIT_BYTES
    )


def swa_flash_attend(cfg: HybridConfig, q, k, v, interpret: bool = False, edge: int | None = None):
    """``swa_attend`` for plain grouped queries under the banded launch: q
    [A, L, H, hd], k and v [A, L, KH, hd] -> [A, L, H * hd]. The queries go
    as the projection left them; K and V a head's tokens contiguous (32 MB
    each at 16k tokens of 8 heads)."""
    from areal_tpu.ops.window_prefill_attention import swa_prefill_flash

    A, L, H, hd = q.shape
    kt, vt = (jnp.swapaxes(t, 1, 2) for t in (k, v))
    return swa_prefill_flash(
        q.reshape(A, L, H * hd), kt, vt, heads=H, window=cfg.sliding_window, sm_scale=cfg.sm_scale, edge=edge, interpret=interpret
    )


# ---------------------------------------------------------------------------
# the layer stack
# ---------------------------------------------------------------------------


def _norm(cfg: HybridConfig, x, w, b=None):
    """The model's norm: RMSNorm, or (``norm_kind`` ``layer``) LayerNorm with
    weight ``w`` and, where the configuration has one (``norm_bias``), bias
    ``b``, the statistics in float32."""
    if cfg.norm_kind != "layer":
        return _rms_norm(x, w, cfg.rms_norm_eps)
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    out = ((x32 - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)).astype(x.dtype) * w
    return out if b is None else out + b


def _final_norm(params: dict, cfg: HybridConfig, x):
    """The norm before the head; a residual path of several streams ends here: their sum (in float32) is normed."""
    if cfg.residual_form == "mhc":
        with jax.named_scope("mhc_merge"):
            x = functools.reduce(jnp.add, _streams(cfg, x)).astype(x.dtype)
    return _norm(cfg, x, params["final_norm"], params.get("final_norm_bias"))


def _norm_in(cfg: HybridConfig, layer: dict, name: str, x):
    """What a sublayer reads: norm(x) in a pre-norm block, x itself where
    the norm stands on the sublayer's output."""
    return _norm(cfg, x, layer[name], layer.get(f"{name}_bias")) if cfg.norm_placement == "pre" else x


def _norm_out(cfg: HybridConfig, layer: dict, name: str, out):
    """What a sublayer adds to the residual stream: its output, normed where
    the block's norms stand there (``h = x + rmsnorm(sublayer(x))``)."""
    return out if cfg.norm_placement == "pre" else _rms_norm(out, layer[name], cfg.rms_norm_eps)


# rows from which a sublayer's stream coefficients are fenced in their batch-on-lanes layout (``mhc_coefficients``)
_MHC_FENCE_ROWS = 1024


def sinkhorn_rows(m: list[list[jax.Array]], rounds: int, eps: float, unrolled: bool | None = None) -> list[list[jax.Array]]:
    """``rounds`` times ``M <- M / (rowsum(M) + eps)``, ``M <- M / (colsum(M) + eps)`` on an n x n matrix a row of
    the batch, handed over as n x n float32 vectors ``m[i][j]`` over the batch. On a TPU a static loop of
    elementwise ops on those vectors (the sums written out, no reduce op): XLA fuses the 2 x ``rounds`` dependent
    passes into one kernel, where a ``lax.fori_loop`` would be a device loop of ``rounds`` trips a sublayer.
    Elsewhere the same round is the body of a ``lax.fori_loop`` (``unrolled`` says which, for a test that holds the
    two to each other to the last bit or two): XLA:CPU takes half a minute to compile the 1,100 ops of the unrolled chain."""
    n = len(m)

    def one_round(m):
        m = [list(row) for row in m]
        for i in range(n):
            s = functools.reduce(jnp.add, m[i]) + eps
            m[i] = [v / s for v in m[i]]
        for j in range(n):
            s = functools.reduce(jnp.add, [m[i][j] for i in range(n)]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
        return m

    if jax.default_backend() == "tpu" if unrolled is None else unrolled:
        for _ in range(rounds):
            m = one_round(m)
        return m
    return jax.lax.fori_loop(0, rounds, lambda _, m: one_round(m), m)


def mhc_coefficients(cfg: HybridConfig, layer: dict, tag: str, X):
    """One sublayer's stream coefficients from the streams X [..., n * D] (stream j on lanes j * D ..), all float32:
    ``x' = x * rsqrt(mean(x^2) + eps)`` over all n * D values (no weight), ``m = x' Phi`` [2n + n^2]; ``H_pre =
    sigmoid(a_pre m[:n] + b_pre)`` [..., n], ``H_post = 2 sigmoid(a_post m[n:2n] + b_post)`` [..., n], ``H_res =
    sinkhorn(exp(clip(a_res m[2n:] + B_res, hc_res_clamp)))`` [..., n * n] row-major (``sinkhorn_rows``). ``tag`` is
    the sublayer (``attn``, ``ffn``): each has leaves of its own. The norm's scale is a number a row, so it is
    taken out of the product: ``m = (x Phi) * rsqrt(...)``, and no normed copy of the streams exists. Past the
    product the batch lies on the LANES (24 vectors over the rows), where the rounds' 1,100 small ops cost
    rows / 1,024 registers each and not rows / 8."""
    n = cfg.hc_mult
    lead = X.shape[:-1]
    with jax.named_scope("mhc_coeff"):
        ms = jnp.mean(jnp.square(X.astype(jnp.float32)), axis=-1, keepdims=True)
        m = jnp.dot(X, layer[f"hc_{tag}_phi"], preferred_element_type=jnp.float32) * jax.lax.rsqrt(ms + cfg.rms_norm_eps)
        cols = m.reshape(-1, m.shape[-1]).T  # [2n + n^2, rows]
        alpha, bias = layer[f"hc_{tag}_alpha"].astype(jnp.float32), layer[f"hc_{tag}_bias"].astype(jnp.float32)
        pre = [jax.nn.sigmoid(alpha[0] * cols[j] + bias[j]) for j in range(n)]
        post = [2.0 * jax.nn.sigmoid(alpha[1] * cols[n + j] + bias[n + j]) for j in range(n)]
        lo, hi = cfg.hc_res_clamp
        z = [[jnp.clip(alpha[2] * cols[2 * n + i * n + j] + bias[2 * n + i * n + j], lo, hi) for j in range(n)] for i in range(n)]
    with jax.named_scope("mhc_sinkhorn"):
        res = sinkhorn_rows([[jnp.exp(v) for v in row] for row in z], cfg.hc_sinkhorn_iters, cfg.hc_eps)
        res = [v for row in res for v in row]
    if math.prod(lead) < _MHC_FENCE_ROWS:
        return tuple(jnp.stack(vs, axis=-1).reshape(*lead, len(vs)) for vs in (pre, post, res))
    # a prompt pass's rows: fenced with the batch still on the lanes. Left to itself XLA:TPU moves the mixes' reshape
    # up through the whole chain and computes every vector of the rounds as the mixes read them, [rows, 1] (one lane
    # of 128 in use, 2 ms a sublayer at 16k rows: compiled for a described v5e, tests/test_tpu_compile.py). A decode
    # step's 64 rows stay unfenced: there the fence splits the rounds into a fusion each (25 launches a sublayer for 7)
    out = jax.lax.optimization_barrier(tuple(jnp.stack(vs, axis=0) for vs in (pre, post, res)))
    return tuple(o.T.reshape(*lead, o.shape[0]) for o in out)


def _streams(cfg: HybridConfig, X) -> list[jax.Array]:
    """The streams of X [..., n * D] as n float32 views [..., D]."""
    D = cfg.hidden_size
    return [X[..., j * D : (j + 1) * D].astype(jnp.float32) for j in range(cfg.hc_mult)]


def mhc_pre(cfg: HybridConfig, X, pre):
    """What a sublayer reads of the streams: ``u = sum_j H_pre[j] X[j]`` [..., D], summed in float32."""
    with jax.named_scope("mhc_pre"):
        xs = _streams(cfg, X)
        return functools.reduce(jnp.add, [pre[..., j : j + 1] * xs[j] for j in range(cfg.hc_mult)]).astype(X.dtype)


def mhc_post(cfg: HybridConfig, X, post, res, out):
    """What a sublayer leaves: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] out`` [..., n * D], summed in float32."""
    n = cfg.hc_mult
    with jax.named_scope("mhc_post"):
        xs, o = _streams(cfg, X), out.astype(jnp.float32)
        new = [
            functools.reduce(jnp.add, [res[..., i * n + j : i * n + j + 1] * xs[j] for j in range(n)]) + post[..., i : i + 1] * o
            for i in range(n)
        ]
        return jnp.concatenate(new, axis=-1).astype(X.dtype)


def _block_in(cfg: HybridConfig, layer: dict, x):
    """What a block's mixer sublayer reads of the carry ``x``, and what the carry's update will need: (x, None) on
    a residual path of one vector; on one of several streams (the pre-mix of the streams under the mixer's own
    coefficients, (the streams, H_post, H_res))."""
    if cfg.residual_form != "mhc":
        return x, None
    pre, post, res = mhc_coefficients(cfg, layer, "attn", x)
    return mhc_pre(cfg, x, pre), (x, post, res)


# bytes of a feed-forward block's widest activations over all rows at once: the
# rows an expert block gathers for its grouped matmuls (rows x experts a token
# x hidden), a dense block's gate and up. Past them the rows go through in
# blocks (a 16k-token prompt of hidden 6144 x top-8 would gather 1.6 GB)
_FFN_BYTES = {"moe": 256 << 20, "dense": 512 << 20}


def ffn_block_rows(cfg: HybridConfig, ffn: str, rows: int) -> int:
    """Rows of a feed-forward block's input that go through at once, from the
    shapes alone: all of them while the widest activations stay inside
    ``_FFN_BYTES`` (every prompt pass of 8 x 1,024 tokens at hidden 2,048 to
    4,096 does), else the largest power-of-two part of ``rows`` that does."""
    wide = cfg.num_experts_per_tok * cfg.hidden_size if ffn == "moe" else 2 * cfg.intermediate_size
    fit = max(1, _FFN_BYTES[ffn] // (wide * jnp.dtype(cfg.dtype).itemsize))
    if rows <= fit:
        return rows
    block = 1 << (fit.bit_length() - 1)
    return block if rows % block == 0 else rows


def _ffn(cfg: HybridConfig, ffn: str, layer: dict, x, live=None, u=None, residual: bool = True):
    """x + the layer's feed-forward block, its RMSNorm where the block has
    it (on the input, or on the output); for an expert block also the rows
    of ``live`` (default: all) each expert got, [E] int32. Where the expert
    leaves come as their stacks (``_scan_layers`` ``whole``), the touched
    experts alone are read. A long prompt's rows go through in blocks
    (``ffn_block_rows``): the block is row-wise, so the result is the same.
    ``u`` is a PARALLEL block's one normed input: the FFN reads it and no
    norm of its own, and ``x`` (the residual stream with the mixer's output
    already on it) takes the FFN's output in the block's one sum.
    ``residual`` False returns the block's output ALONE, ``FFN(norm(x))``:
    what a residual path of several streams writes back through its own mix
    (``_block_ffn``)."""
    rm = cfg.residual_multiplier
    n_rows = x.size // x.shape[-1]
    block = ffn_block_rows(cfg, ffn, n_rows)
    if block < n_rows:
        lv = jnp.ones((n_rows,), bool) if live is None else live.reshape(-1)
        rows = (x.reshape(-1, block, x.shape[-1]), lv.reshape(-1, block)) + (() if u is None else (u.reshape(-1, block, u.shape[-1]),))
        out, load = jax.lax.map(lambda a: _ffn(cfg, ffn, layer, *a, residual=residual), rows)
        return out.reshape(x.shape), (None if load is None else load.sum(0))
    if ffn == "dense":
        assert u is None, "a parallel block with a dense FFN is not implemented"
        with jax.named_scope("mlp"):
            h = _norm_in(cfg, layer, "post_norm", x)
            if cfg.fused_gate_up:
                g, u = jnp.split(_proj(cfg, layer, "w_gate_up", h), 2, axis=-1)
            else:
                g, u = _proj(cfg, layer, "w_gate", h), _proj(cfg, layer, "w_up", h)
            out = _norm_out(cfg, layer, "post_norm", _proj(cfg, layer, "w_down", jax.nn.silu(g) * u))
            return (x + rm * out if residual else out), None
    with jax.named_scope("moe_router"):
        h = _norm_in(cfg, layer, "post_norm", x) if u is None else u
    rows = h.reshape(-1, h.shape[-1])
    out, _, _, load = moe.expert_ffn(
        rows, layer, cfg, live=None if live is None else live.reshape(-1), e0=cfg.expert_first
    )
    if "ws_gate" in layer:  # the always-active block: every row, gate 1
        with jax.named_scope(MOE_SHARED_SCOPE):
            shared = jax.nn.silu(_proj(cfg, layer, "ws_gate", rows)) * _proj(cfg, layer, "ws_up", rows)
            shared = _proj(cfg, layer, "ws_down", shared).astype(out.dtype)
            # several shared experts side by side: the down projection summed over all of their columns, their MEAN
            out = out + (shared if cfg.moe_shared_mean_of == 1 else shared * (1.0 / cfg.moe_shared_mean_of))
    if u is not None:
        with jax.named_scope("block_sum"):  # x + Mixer(u) came in as ``x``: the block's one sum
            return x + rm * out.reshape(x.shape).astype(x.dtype), load
    with jax.named_scope("moe_combine"):
        out = _norm_out(cfg, layer, "post_norm", out.reshape(x.shape).astype(x.dtype))
        return (x + rm * out if residual else out), load


# the scope a mixer's norm counts under (its projections')
def _block_ffn(cfg: HybridConfig, ffn: str, layer: dict, x, out, h, live, streams=None):
    """A block's second half: ``x`` the block's input, ``out`` its mixer's
    output, ``h`` what the mixer read. Serial: ``h' = x + out``, ``h' +
    FFN(norm(h'))``. Parallel: ``x + out + FFN(h)``, ``h`` the block's ONE
    normed input. On a residual path of several streams (``streams`` = what
    ``_block_in`` kept: the streams X, H_post, H_res; ``x`` is then the
    mixer's pre-mix): ``X' = H_res X + H_post out``, then the FFN sublayer
    under coefficients of its own, ``X'' = H_res' X' + H_post' FFN(norm(sum_j
    H_pre'[j] X'[j]))``."""
    rm = cfg.residual_multiplier
    if streams is not None:
        X = mhc_post(cfg, *streams, out)
        pre, post, res = mhc_coefficients(cfg, layer, "ffn", X)
        f, load = _ffn(cfg, ffn, layer, mhc_pre(cfg, X, pre), live, residual=False)
        return mhc_post(cfg, X, post, res, f), load
    if cfg.block_form == "parallel":
        return _ffn(cfg, ffn, layer, x + rm * out, live, u=h)
    return _ffn(cfg, ffn, layer, x + rm * out, live)


_MIXER_SCOPE = {
    "mamba": "ssm_proj", "gdn": "gdn_proj", "conv": "conv_proj", "attention": "attn_proj", "mla": "mla_proj",
    "s6": "ssm_proj", "swa": "attn_proj", "cross": "attn_proj", "gmu": "gmu", "kda": "kda_proj",
}


def _runs(cfg: HybridConfig) -> list[tuple[str, str, int, int, int, int]]:
    """Runs of consecutive layers of one kind, in model order: (mixer, FFN,
    first index within the params stack, within the mixer kind, within the
    FFN kind, count)."""
    out: list[tuple[str, str, int, int, int, int]] = []
    seen: dict[str, int] = {}
    for kind, ffn in _layer_kinds(cfg):
        stack = stack_name(kind, ffn)
        if out and out[-1][:2] == (kind, ffn):
            out[-1] = (*out[-1][:5], out[-1][5] + 1)
        else:
            out.append((kind, ffn, seen.get(stack, 0), seen.get(kind, 0), seen.get(ffn, 0), 1))
        for key in {stack, kind, ffn}:
            seen[key] = seen.get(key, 0) + 1
    return out


def _periods(cfg: HybridConfig) -> list[tuple[tuple[tuple[str, str, int, int, int, int], ...], int, int, int]]:
    """The runs as ``_scan_layers`` walks them: (the runs of one period, how
    many periods, the first layer's model index, layers). A run of one kind
    is a period of itself. Where single layers of TWO kinds alternate (a, b,
    a, b, ...) twice or more, the pairs are one group: one scan body of two
    layers, not a body a layer (a decoder-hybrid-decoder alternates all the
    way down: 32 bodies to trace and compile otherwise)."""
    runs = _runs(cfg)
    out, r, at = [], 0, 0
    while r < len(runs):
        pair = runs[r : r + 2]
        n = 0
        if len(pair) == 2 and pair[0][5] == pair[1][5] == 1 and pair[0][:2] != pair[1][:2]:
            while all(
                r + 2 * n + e < len(runs) and runs[r + 2 * n + e][5] == 1 and runs[r + 2 * n + e][:2] == pair[e][:2]
                for e in (0, 1)
            ):
                n += 1
        if n >= 2:
            out.append((tuple(pair), n, at, 2 * n))
            r, at = r + 2 * n, at + 2 * n
        else:
            out.append(((runs[r],), runs[r][5], at, runs[r][5]))
            r, at = r + 1, at + runs[r][5]
    return out


def _scan_layers(cfg: HybridConfig, params: dict, carry, step, whole: tuple[str, ...] = (), layers: tuple[int, int] | None = None):
    """Run ``step(kind, ffn, carry, layer, j, f) -> carry`` over the layers in
    model order; ``layer`` is the layer's slice of its stack, ``j`` its index
    among the layers of its mixer kind (the state's and the KV pool's layer
    axis) and ``f`` among those of its FFN kind, both traced. One
    ``lax.scan`` per run of one kind, or per group of alternating pairs
    (``_periods``). ``layers`` = (first, end) walks that range of model
    indices alone; it may not cut a run.

    A leaf named in ``whole`` is handed over as ``moe.Stacked``, the stack
    and the layer's index in it, as the page pools and the recurrent state go
    by ``j``: for a Pallas launch that reads its layer where it lies."""
    first, end = layers or (0, cfg.num_layers)

    def layer_at(stack, i):
        sliced = functools.partial(jax.lax.dynamic_index_in_dim, index=i, axis=0, keepdims=False)
        return {k: moe.Stacked(a, i) if k in whole else jax.tree.map(sliced, a) for k, a in stack.items()}

    for group, n, at, span in _periods(cfg):
        if at + span <= first or at >= end:
            continue
        assert first <= at and at + span <= end, f"layers {first}..{end} cut the run at {at}..{at + span}"
        if len(group) == 1:
            kind, ffn, lo, lo_kind, lo_ffn, _ = group[0]
            stack = params[stack_name(kind, ffn)]

            def body(c, i, kind=kind, ffn=ffn, stack=stack, dj=lo_kind - lo, df=lo_ffn - lo):
                return step(kind, ffn, c, layer_at(stack, i), i + dj if dj else i, i + df if df else i), None

            carry, _ = jax.lax.scan(body, carry, jnp.arange(lo, lo + n, dtype=jnp.int32))
            continue
        # how far a period moves each index: by its layers of that FFN kind (the mixers, and so the stacks, differ)
        ffn_stride = {ffn: sum(1 for r in group if r[1] == ffn) for _, ffn, *_ in group}

        def body(c, i, group=group, ffn_stride=ffn_stride):
            for kind, ffn, lo, lo_kind, lo_ffn, _ in group:
                c = step(kind, ffn, c, layer_at(params[stack_name(kind, ffn)], lo + i), lo_kind + i, lo_ffn + ffn_stride[ffn] * i)
            return c, None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(n, dtype=jnp.int32))
    return carry


def _embed(params: dict, cfg: HybridConfig, ids):
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], ids, cfg.jax_dtype, batch_sharded=False)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.residual_form == "mhc":  # the streams are born as copies of the embedding, side by side on the lanes
            x = jnp.concatenate([x] * cfg.hc_mult, axis=-1)
        return x


def _rotate_qk(cfg: HybridConfig, q, k, positions):
    """The rotary embedding of q [..., H, hd] and k [..., KH, hd] at
    ``positions`` [...]: halves, or with ``rope_interleave`` channels (2i,
    2i+1), which leaves both as [evens | odds] (``_pairs_to_halves``): the
    same permutation on both sides of every q . k, so a cached key is read as
    it lies."""
    if cfg.rope_interleave:
        q, k = _pairs_to_halves(q), _pairs_to_halves(k)
    return _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)


def _qkv(cfg: HybridConfig, layer: dict, h, positions, rotate: bool = True, fence: bool = False):
    """q [..., H, hd], k and v [..., KH, hd] of the layer's input h [..., D]
    at ``positions`` [...]: RMSNorm of q and k (over each head, or over the
    whole projection), then the rotary embedding, where the configuration
    has them for an ``attention`` layer (``rotates``; a window layer passes
    ``rotate`` False and turns its own under a scope of their own,
    ``attn_rope``). ``fence`` keeps each
    projection ONE matmul whose output is split into heads afterwards: left
    to itself XLA:TPU folds the split (and a rotary permutation behind it)
    into ``W_q``, wants the weight in another layout and copies the whole
    layer STACK of it, once a program (0.54 GB at four layers of [4096,
    16384]: compiled for a described v5e, tests/test_tpu_compile.py;
    ``_mla_in`` has the same). A parallel block's layers pass it; the serial
    families' programs stay as their cells measured them."""
    lead = h.shape[:-1]
    whole = cfg.qk_norm and cfg.qk_norm_over == "whole"

    def heads(w, norm, n):  # a projection, normed over its whole width where the configuration says so, split into heads
        x = _proj(cfg, layer, w, h)
        if fence:
            x = jax.lax.optimization_barrier(x)
        if whole and norm:
            x = _rms_norm(x, layer[norm], cfg.rms_norm_eps)
        return x.reshape(*lead, n, cfg.head_dim_)

    q = heads("wq", "q_norm", cfg.num_heads)
    k = heads("wk", "k_norm", cfg.num_kv_heads)
    v = heads("wv", None, cfg.num_kv_heads)
    if cfg.qk_norm and cfg.qk_norm_over == "head":
        q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if rotate and cfg.rotates("attention"):
        q, k = _rotate_qk(cfg, q, k, positions)
    return q, k, v


def _lane_pad(cfg: HybridConfig, t):
    pad = cfg.kv_head_dim - cfg.head_dim_
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, pad),)) if pad else t


# float32 [H, L, L] logits a row of the ``attention`` mixer's XLA prompt pass may hold: 64 heads pass it at 2,048
# tokens (1 GB; 68 GB at 16k), 30 heads of a 1,024-token prompt (126 MB) do not
_PREFILL_GQA_LOGIT_BYTES = 512 << 20


def gqa_prefill_launch(cfg: HybridConfig, L: int) -> bool:
    """Whether the ``attention`` mixer's prompt pass of bucket ``L`` attends
    under ``ops/attention.py flash_fwd_pallas`` on a TPU, from the shapes
    alone (``prefill_takes_launch``'s way): where a row's [H, L, L] float32
    logits pass ``_PREFILL_GQA_LOGIT_BYTES`` and the kernel serves the shape
    (heads of 128 lanes unpadded, a row of whole 256-token tiles). Below
    that the XLA form stays: the CPU path, and the launch's oracle."""
    return (
        jax.default_backend() == "tpu"
        and not cfg.diff_attn
        and cfg.head_dim_ == cfg.kv_head_dim == 128
        and L % 256 == 0
        and 4 * cfg.num_heads * L * L > _PREFILL_GQA_LOGIT_BYTES
    )


def gqa_flash_attend(cfg: HybridConfig, q, k, v, seg, interpret: bool = False):
    """Causal grouped-query attention over whole prompts under the flash
    launch: q [A, L, H, hd], k and v [A, L, KH, hd], seg [A, L] (1 = a
    prompt's token; a padding row reads zeros). One launch a row and KV
    head, its group's queries against that head's keys and values handed
    once a query head (the launch takes KV heads replicated: 8 x 4 MB at 16k
    tokens a launch, never 64 heads of them). Nothing of [H, L, L] exists.
    Returns [A, L, H * hd]."""
    from areal_tpu.ops.attention import flash_fwd_pallas

    A, L, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = jnp.moveaxis(q.reshape(A, L, KH, G, hd), 2, 1).reshape(A * KH, L, G, hd)
    kg, vg = (jnp.moveaxis(t, 2, 1).reshape(A * KH, L, 1, hd) for t in (k, v))
    segs = jnp.repeat(seg.astype(jnp.int32), KH, axis=0)

    def one(args):
        q1, k1, v1, s1 = args
        k1, v1 = (jnp.broadcast_to(t, (L, G, hd)) for t in (k1, v1))
        return flash_fwd_pallas(q1[None], k1[None], v1[None], s1[None], interpret=interpret)[0]

    out = jax.lax.map(one, (qg, kg, vg, segs)).reshape(A, KH, L, G, hd)
    return jnp.moveaxis(out, 1, 2).reshape(A, L, H * hd)


_ATTN_GATE_ROWS = 2048


def _attn_gated(cfg: HybridConfig, layer: dict, attn, h):
    """An ``attention`` layer's output before ``W_o``: ``attn * sigmoid(W_g
    h)``, one gate a head and channel, where the configuration has the gate."""
    if not cfg.attn_gate:
        return attn

    def gated(a):
        gate = jax.nn.sigmoid(_proj(cfg, layer, "wg", a[1]).astype(jnp.float32))
        return (a[0].astype(jnp.float32) * gate).astype(attn.dtype)

    with jax.named_scope(ATTN_GATE_SCOPE):
        rows = attn.size // attn.shape[-1]
        if rows <= _ATTN_GATE_ROWS or rows % _ATTN_GATE_ROWS:
            return gated((attn, h))
        # a long prompt's rows in blocks: the float32 gate of 16k rows x 8,192 channels is 0.5 GB, and its product as much again
        blocks = (attn.reshape(-1, _ATTN_GATE_ROWS, attn.shape[-1]), h.reshape(-1, _ATTN_GATE_ROWS, h.shape[-1]))
        return jax.lax.map(gated, blocks).reshape(attn.shape)


def compute_logits(params: dict, cfg: HybridConfig, hidden: jax.Array) -> jax.Array:
    return qwen.compute_logits(params, cfg, hidden) / cfg.logits_scaling


def forward_prefill(
    params: dict,
    cfg: HybridConfig,
    input_ids: jax.Array,  # [A, L]
    seg: jax.Array,  # [A, L] 1=valid 0=pad
    n_state: jax.Array | None = None,  # [A] tokens that enter the state; default all valid
    sink: tuple | None = None,
    tail: str = "all",
):
    """Batched prompt pass. Returns (hidden [A, L, D], ks, vs
    [n_attention, A, L, KH, kv_head_dim], state); for a latent-attention
    model ks is the latent rows [n_mla, A, L, 1, latent_lanes] (computed in
    the PLAIN form: per-head keys and values from the prompt's own latent)
    and vs None, or where the layers have an index its keys [n_mla, A, L, 1,
    index_head_dim]. ``state`` is the
    recurrent state after each row's first ``n_state`` tokens, stacked per
    layer of its mixer kind ({leaf: [n, A, ...]}, ``cfg.state_shapes``).

    ``sink = (arrays, write)`` replaces the stacked state: ``arrays`` is
    carried through the layers and ``write(arrays, j, {leaf: new})`` stores
    layer j's state into it (the engine writes straight into its cache's
    slot rows, so no second copy of A states exists). A sink that holds
    ``k`` also takes a latent model's rows layer by layer, ``{"k": rows
    [A, L, 1, latent_lanes]}`` (and ``"idx"``: the index keys), and ks and vs
    are then None. A sink that holds the window layers' rings takes their K
    and V rows layer by layer too (``{"ring_k", "ring_v"}: [A, L, heads,
    lanes]``: which of them a ring keeps is the sink's to say).

    ``tail`` is a decoder-hybrid-decoder's: what the layers PAST the one
    whose keys and values they read compute. ``all``: every token, as every
    other model (quadratic in L: tests and short prompts). ``last``: the
    architecture's own prompt pass: the self-decoder and the shared layer's
    K and V over every token, its attention and the cross-decoder over each
    row's LAST valid token alone; hidden is [A, 1, D]. ``none``: the same
    without the last token's row, hidden None: what the engine's prefill
    program needs, since its first decode step feeds the last prompt token
    again."""
    A, L = input_ids.shape
    if n_state is None:
        n_state = jnp.sum(seg, axis=-1)
    n_state = n_state.astype(jnp.int32)
    shapes = cfg.state_shapes(A)
    dtypes = {k: d for k, (_, d) in shapes.items()}
    if sink is None:
        arrays = {k: jnp.zeros(shp, d) for k, (shp, d) in shapes.items()}

        def write(arr, j, new):
            return {**arr, **{k: arr[k].at[j].set(v) for k, v in new.items()}}
    else:
        arrays, write = sink
    # what every token leaves behind in the layers that attend, pool by pool:
    # K and V rows, or a latent row (and, where the layer has an index, its
    # key: it rides in the V rows' place)
    latent = "v" not in cfg.kv_pools
    second = cfg.kv_pools.get("idx" if latent else "v")
    k_shape, v_shape = ((cfg.num_kv_layers, A, L, *pool) if pool else () for pool in (cfg.kv_pools["k"], second))
    rows_to_sink = latent and "k" in arrays  # the sink takes the latent rows too
    # a latent layer makes its own masks a block at a time: no [A, 1, L, L] for a 16k prompt
    # the plain ``attention`` mixer under the flash launch where [H, L, L] would not fit: the launch masks by ``seg``
    gqa_launch = bool(cfg.count("attention")) and gqa_prefill_launch(cfg, L)
    swa_launch = swa_prefill_launch(cfg, L)  # the window layers' band under its launch where a block's logits would not fit
    mask = None if latent or cfg.diff_attn or gqa_launch else qwen._attention_mask(seg)  # (differential attention masks by position, a row at a time)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (A, L))
    live = seg.astype(bool)
    # a decoder-hybrid-decoder: the one layer whose K and V the cross layers read, and where the rows narrow to each
    # prompt's last token (``tail``)
    shared = cfg.layers_of("attention")[0] if cfg.count("cross") else None
    assert tail == "all" or shared is not None, "tail is a decoder-hybrid-decoder's"
    depth_of = _depths(cfg)
    last_at = jnp.maximum(jnp.sum(seg, axis=-1).astype(jnp.int32) - 1, 0)  # [A]

    def at_last(t):  # [A, L, ...] -> [A, 1, ...]: each row's last valid token
        return jnp.take_along_axis(t, last_at.reshape(A, *(1,) * (t.ndim - 1)), axis=1)

    def diff_rows(q, k, v, q_pos):  # causal differential attention a row at a time: q [A, T, H, hd] at positions q_pos [A, T]
        return jax.lax.map(lambda a: diff_attend(cfg, a[0], a[1], a[2], jnp.arange(L)[None, :] <= a[3][:, None]), (q, k, v, q_pos))

    def attend(args):  # one row at a time: [H, L, L] logits, not [A, H, L, L]
        q, k, v, m = args
        G = cfg.num_heads // cfg.num_kv_heads
        qg = q.reshape(L, cfg.num_kv_heads, G, cfg.head_dim_)
        logits = jnp.einsum("tkgd,skd->kgts", qg, k).astype(jnp.float32) * cfg.sm_scale
        logits = jnp.where(m[0][None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(L, cfg.q_dim)

    def step(kind, ffn, carry, layer, j, f, narrow=False):
        x, ks, vs, arr = carry
        x, streams = _block_in(cfg, layer, x)  # several residual streams: the mixer reads their pre-mix
        q_pos = last_at[:, None] if narrow else positions  # where the rows of x stand
        if kind == "mamba":
            h = _norm_in(cfg, layer, "input_norm", x)
            out, ssm, conv = mamba_prefill(cfg, layer, h, n_state, (dtypes["ssm"], dtypes["conv"]))
            with jax.named_scope("state_write"):
                arr = write(arr, j, {"ssm": ssm, "conv": conv})
        elif kind == "s6":
            with jax.named_scope("ssm_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, ssm, conv, y = s6_prefill(cfg, layer, h, n_state, (dtypes["ssm"], dtypes["conv"]))
            with jax.named_scope("state_write"):
                arr = write(arr, j, {"ssm": ssm, "conv": conv})
                if "gmu_m" in arr:  # the LAST such layer's stays: what the memory units read
                    arr = {**arr, "gmu_m": y if tail == "all" else at_last(y)}
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out = gmu_mix(cfg, layer, h, arr["gmu_m"])
        elif kind == "swa" and not cfg.diff_attn:
            # plain grouped queries over the band; the keys go into the ring ALREADY rotated, so a read takes them as they lie
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = _qkv(cfg, layer, h, positions, rotate=False, fence=True)
            if cfg.rotates("swa"):
                with jax.named_scope("attn_rope"):
                    q, k = _rotate_qk(cfg, q, k, positions)
            if "ring_k" in arr:
                with jax.named_scope("kv_write"):
                    arr = write(arr, j, {"ring_k": _lane_pad(cfg, k), "ring_v": _lane_pad(cfg, v)})
            with jax.named_scope("attn_window"):
                attn = (swa_flash_attend if swa_launch else swa_attend)(cfg, q, k, v)
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        elif kind == "swa":
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = _diff_qkv(cfg, layer, h)
            if "ring_k" in arr:
                with jax.named_scope("kv_write"):
                    arr = write(arr, j, {"ring_k": k, "ring_v": v})
            with jax.named_scope("attn_window"):
                o = swa_attend(cfg, q, k, v)
            o = _diff_merge(cfg, layer, o, depth_of["swa"][j], x.dtype)
            with jax.named_scope("attn_proj"):
                out = _diff_out(cfg, layer, o)
        elif kind == "cross":
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, _, _ = _diff_qkv(cfg, layer, h, kv=False)
            with jax.named_scope("attn_cross"):
                o = diff_rows(q, ks[0], vs[0], q_pos)
            o = _diff_merge(cfg, layer, o, depth_of["cross"][j], x.dtype)
            with jax.named_scope("attn_proj"):
                out = _diff_out(cfg, layer, o)
        elif kind == "attention" and cfg.diff_attn:
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                # the keys and values of EVERY token; the queries of the rows that go on (``narrow``: the last alone)
                _, k, v = _diff_qkv(cfg, layer, h, query=False)
                ks, vs = ks.at[j].set(k), vs.at[j].set(v)
                if narrow:
                    x, h = at_last(x), at_last(h)
                q, _, _ = _diff_qkv(cfg, layer, h, kv=False)
            with jax.named_scope("attn"):
                o = diff_rows(q, k, v, q_pos)
            o = _diff_merge(cfg, layer, o, depth_of["attention"][j], x.dtype)
            with jax.named_scope("attn_proj"):
                out = _diff_out(cfg, layer, o)
        elif kind == "gdn":
            with jax.named_scope("gdn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, gdn, conv = gdn_prefill(cfg, layer, h, n_state, (dtypes["gdn"], dtypes["conv"]))
            with jax.named_scope("state_write"):
                arr = write(arr, j, {"gdn": gdn, "conv": conv})
        elif kind == "kda":
            with jax.named_scope("kda_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, kda, conv = kda_prefill(cfg, layer, h, n_state, (dtypes["kda"], dtypes["conv"]))
            with jax.named_scope("state_write"):
                arr = write(arr, j, {"kda": kda, "conv": conv})
        elif kind == "conv":
            with jax.named_scope("conv_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, conv = conv_prefill(cfg, layer, h, n_state, dtypes["conv"])
            with jax.named_scope("state_write"):
                arr = write(arr, j, {"conv": conv})
        elif kind == "mla":
            with jax.named_scope("mla_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            # a low-rank query's heads are made a block of queries at a time (mla_prefill_attend)
            q_nope, q_rope, c, k_r, q_r = _mla_in(cfg, layer, h, positions, query=not cfg.q_lora_rank)
            with jax.named_scope("mla_proj"):
                new = {"k": _latent_row(cfg, c, k_r)}
            if cfg.index_topk:
                k_i = index_key(cfg, layer, h, positions)
                new["idx"] = k_i[..., None, :]
            if rows_to_sink:  # layer by layer into the caller's pages: no [layers, A, L, lanes] buffer beside them
                with jax.named_scope("kv_write"):
                    arr = write(arr, j, new)
            else:
                ks = ks.at[j].set(new["k"])
                vs = vs.at[j].set(new["idx"]) if cfg.index_topk else vs
            rows = (q_r if cfg.q_lora_rank else (q_nope, q_rope), c, k_r) + (((h, k_i),) if cfg.index_topk else ())
            out = jax.lax.map(lambda a: mla_prefill_attend(cfg, layer, *a), rows)  # one prompt at a time
        else:
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = _qkv(cfg, layer, h, positions, fence=cfg.block_form == "parallel")
                ks = ks.at[j].set(_lane_pad(cfg, k))
                vs = vs.at[j].set(_lane_pad(cfg, v))
            with jax.named_scope("attn"):
                attn = gqa_flash_attend(cfg, q, k, v, seg) if gqa_launch else jax.lax.map(attend, (q, k, v, mask))
            attn = _attn_gated(cfg, layer, attn, h)
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        with jax.named_scope(_MIXER_SCOPE[kind]):
            out = _norm_out(cfg, layer, "input_norm", out)
        x, _ = _block_ffn(cfg, ffn, layer, x, out, h, None if narrow else live, streams)
        return x, ks, vs, arr

    x = _embed(params, cfg, input_ids)
    if cfg.count("gmu") and tail != "none":  # the scan output the memory units read rides with the state
        arrays = {**arrays, "gmu_m": jnp.zeros((A, L if tail == "all" else 1, cfg.s6_d_inner), jnp.float32)}
    # a latent model has no V rows, and none to collect where the sink takes
    # them: a scalar rides in their place
    carry = (
        x,
        jnp.zeros(() if rows_to_sink else k_shape, cfg.jax_dtype),
        jnp.zeros(() if rows_to_sink else v_shape, cfg.jax_dtype),
        arrays,
    )
    # a prompt pass's rows go through grouped matmuls (moe.takes_dense_form says when), which read the expert
    # stacks where they lie: a layer's three matrices sliced out for them would be copied, once a layer
    routed = cfg.num_moe_layers and not moe.takes_dense_form(ffn_block_rows(cfg, "moe", A * L), cfg.num_experts)
    if tail == "all":
        x, ks, vs, arrays = _scan_layers(cfg, params, carry, step, whole=moe.EXPERT_LEAVES if routed else ())
    else:
        # the self-decoder over every token; the shared layer's K and V over every token and, with the
        # cross-decoder behind it, each row's last token alone
        x, ks, vs, arrays = _scan_layers(cfg, params, carry, step, layers=(0, shared))
        layer = jax.tree.map(lambda a: a[0], params[stack_name("attention", cfg.ffns[shared])])
        if tail == "none":
            with jax.named_scope("attn_proj"):
                _, k, v = _diff_qkv(cfg, layer, _norm_in(cfg, layer, "input_norm", x), query=False)
            return None, ks.at[0].set(k), vs.at[0].set(v), arrays
        carry = step("attention", cfg.ffns[shared], (x, ks, vs, arrays), layer, 0, 0, narrow=True)
        x, ks, vs, arrays = _scan_layers(cfg, params, carry, functools.partial(step, narrow=True), layers=(shared + 1, cfg.num_layers))
    arrays = {k: v for k, v in arrays.items() if k != "gmu_m"}
    with jax.named_scope("lm_head"):
        hidden = _final_norm(params, cfg, x)
    return hidden, (None if rows_to_sink else ks), (vs if v_shape and not rows_to_sink else None), arrays


def prefill_into_cache(
    params: dict,
    cfg: HybridConfig,
    cache: dict,
    ids: jax.Array,  # [A, bucket]
    plens: jax.Array,  # [A]
    flat_pages: jax.Array,  # [A * bucket/psz]
    slots: jax.Array,  # [A] target slot per row; past the last slot for a padding row
    *,
    page_size: int,
    image_embeds: jax.Array | None = None,
) -> dict:
    """What the engine's prefill program does for this family: the K and V
    of every prompt token into the rows' pages (a row written twice is
    harmless), and into each row's slot the recurrent state after the tokens
    BEFORE the prompt's last one. Decode feeds that last token again, at its
    own position, and a state cannot take a token twice: so the last token,
    everything after it and the padding up to the bucket are masked out of
    the state here, not left to be overwritten."""
    from areal_tpu.inference import paged_kv

    assert image_embeds is None, "the hybrid family has no vision tower"
    bucket = ids.shape[1]
    seg = (jnp.arange(bucket, dtype=jnp.int32)[None] < plens[:, None]).astype(jnp.int32)
    if "v" not in cfg.kv_pools:
        # a latent model (no slot state): its rows go into their pages layer
        # by layer; 48 layers of 8 x 1024 rows collected first are 0.5 GB, and
        # as much again to scatter
        def put(arr, j, new):  # every pool of the layer: the latent rows and, beside them, the index keys
            return {n: paged_kv.scatter_prefill_layer(arr[n], j, rows, flat_pages, page_size) for n, rows in new.items()}

        *_, pages = forward_prefill(params, cfg, ids, seg, sink=({n: cache[n] for n in cfg.kv_pools}, put))
        return {**cache, **pages}
    state = {k: cache[k] for k in paged_kv.STATE_LEAVES + paged_kv.RING_LEAVES if k in cache}
    # a state leaf is [layers, slots, ...], a ring [layers, heads, slots + 1, ...] (the block past the last slot takes
    # a padding row's): a model may hold either alone
    n_slots = next(
        (v.shape[1] if k in paged_kv.STATE_LEAVES else v.shape[2] - 1 for k, v in state.items()), 0
    )

    def write(arr, j, new):
        # one dynamic-update-slice a row, a padding row rewriting what its
        # (clamped) slot holds. Not a scatter: on the v5e a prefill of 4 rows
        # of 1024 with `.at[j, slots].set(mode="drop")` here never ended once
        # other prefill programs had run in the process (PERF.md, PR 26)
        arr = dict(arr)
        for i in range(ids.shape[0]):
            at = jnp.minimum(slots[i], n_slots - 1)
            for name, rows in new.items():
                if name in paged_kv.RING_LEAVES:
                    # a window layer's rows [A, L, heads, lanes]: the slot's ring takes the last ``sliding_window`` of
                    # the prompt's tokens, token t at ring position t % sliding_window (a padding row: the block past
                    # the last slot)
                    # ONE HEAD AN UPDATE: a window that spans (heads, lanes) makes the TPU compiler lay the whole carried
                    # ring out head-minor, and copy it in and out of the loop (paged_kv.write_decode_rows has the same)
                    ring = paged_kv.ring_of_rows(rows[i], plens[i], cfg.sliding_window, arr[name].shape[3:5]).astype(arr[name].dtype)
                    for head in range(ring.shape[0]):
                        start = (j, head, jnp.minimum(slots[i], n_slots), 0, 0, 0)
                        arr[name] = jax.lax.dynamic_update_slice(arr[name], ring[None, head : head + 1, None], start)
                    continue
                start = (j, at) + (0,) * (rows.ndim - 1)
                old = jax.lax.dynamic_slice(arr[name], start, (1, 1) + rows.shape[1:])
                row = jnp.where(slots[i] < n_slots, rows[i][None, None].astype(old.dtype), old)
                arr[name] = jax.lax.dynamic_update_slice(arr[name], row, start)
        return arr

    # a decoder-hybrid-decoder's prompt pass ends at the shared layer's K and V: the decode step that feeds the last
    # prompt token again is the ONE row of its cross-decoder a prompt costs
    tail = "none" if cfg.count("cross") else "all"
    _, ks, vs, state = forward_prefill(params, cfg, ids, seg, n_state=plens - 1, sink=(state, write), tail=tail)
    with jax.named_scope("kv_write"):
        cache = paged_kv.scatter_prefill(
            {k: v for k, v in cache.items() if k not in state}, ks, vs, flat_pages, page_size
        )
    return {**cache, **state}


def slot_state_view(cfg: HybridConfig, leaf: str, rows: jax.Array) -> jax.Array:
    """Rows [..., slot state] of the cache's state leaf ``leaf`` in the
    mixer's own order of axes: the delta-rule state ``gdn`` unpacked to
    [..., H, K, V]; the other leaves (``kda`` [..., H, K, V] among them) lie
    as their mixer reads them."""
    if leaf == "gdn":
        from areal_tpu.ops.gdn_state_update import unpack_state

        return unpack_state(rows, cfg.gdn_head_pack)
    return rows


def first_layer_selection(params: dict, cfg: HybridConfig, ids, positions, cache: dict, page_table, *, page_size: int, use_kernel: bool):
    """What the FIRST layer's index selects for the tokens ``ids`` [S] at
    ``positions`` [S] over each row's cached index keys (``page_table`` [S,
    wp]: the pages that hold them, ``positions + 1`` tokens each): bool [S,
    wp * page_size], by the routine a decode step runs (``index_select``).
    The first layer reads the token's embedding alone, so nothing but the
    cached keys enters from the past: what a check of the selection needs."""
    layer = jax.tree.map(lambda a: a[0], params[stack_name(*_layer_kinds(cfg)[0])])
    lengths = (positions + 1).astype(jnp.int32)
    h = _norm_in(cfg, layer, "input_norm", _embed(params, cfg, ids))
    *_, q_r = _mla_in(cfg, layer, h, positions, query=False)
    q_i, w_i = index_query(cfg, layer, h, q_r, positions)
    kernel = None
    if use_kernel:
        from areal_tpu.inference import paged_kv
        from areal_tpu.ops.paged_attention_q8 import decode_schedule

        ppcb = paged_kv.choose_ppcb(page_table.shape[1])
        kernel = dict(pages_per_compute_block=ppcb, schedule=decode_schedule(lengths, page_table.shape[1], page_size, ppcb))
    return index_select(cfg, q_i, w_i, cache["idx"], jnp.int32(0), lengths, page_table, page_size, kernel)


_NO_STATE_CUT = (
    "needs a recurrent state cut back to a token boundary, which does not exist for state-space, "
    "short-conv or delta-rule layers (ROADMAP Reach A.7: state snapshots at page boundaries)"
)
_NO_LATENT_SUFFIX = (
    "needs an attention pass of new tokens over cached LATENT pages, which this module does not "
    "have (ROADMAP Reach A.5: a suffix kernel over latent pages)"
)


def _refuse(what: str):
    def refuse(*_a, **_k):
        raise NotImplementedError(
            f"{what} is not implemented for the hybrid family: over a recurrent state it {_NO_STATE_CUT}; "
            f"over latent pages it {_NO_LATENT_SUFFIX}"
        )

    return refuse


forward_prefill_paged = _refuse("suffix prefill over a cached prefix")
forward_verify_paged = _refuse("speculative verification")


def quantize_params_int8(params: dict) -> dict:
    raise NotImplementedError("int8 weight quantization is not implemented for the hybrid family's mixer")


def serving_limits(cfg: HybridConfig) -> dict[str, str]:
    """What this module does not implement for ``cfg``, for the decode engine
    to refuse when it is configured: {feature: why} over ``prefix_cache``
    (radix matching, and the suffix prefill behind a hit), ``speculative``,
    ``int8_weights`` and ``sharded``, beside ``reason``, the one word
    ``/statusz`` gives for it. Every model of this family has all four: the
    module has no ``forward_prefill_paged`` / ``forward_verify_paged``, no
    int8 form of its mixers and experts, and no sharded form."""
    if cfg.has_recurrent_state:
        rings = (
            {"int8_pages": "quantized pages are not implemented for the window layers' rings; serve this model with kv_quantization='none'"}
            if cfg.count("swa")
            else {}
        )
        return {
            **rings,
            "reason": "recurrent_state",
            "prefix_cache": "the model has recurrent (state-space) layers and a cached page prefix carries no state",
            "speculative": (
                "speculative decoding cannot serve a model with recurrent (state-space) layers: a rejected "
                "draft would have to roll the slot's state back, and no state snapshot exists"
            ),
            "int8_weights": (
                "int8 weight quantization is not implemented for the hybrid family's mixers and experts; "
                "serve this model with quantization='none'"
            ),
            "sharded": (
                "a model with recurrent (state-space) layers serves on one chip a replica: its mixer and "
                "state are not sharded"
            ),
        }
    if cfg.count("swa"):
        # the window layers' rings are the slot's ONLY tenant beside its pages: no state to roll back, but a cached
        # prefix of the full layers' pages says nothing of the last ``sliding_window`` keys a ring must hold behind it
        return {
            "reason": "window_rings",
            "prefix_cache": (
                "the model's window layers keep their keys in a ring of the slot's own, and a cached page prefix of "
                "the full layers carries no ring (ROADMAP Reach A.6: a ring rebuilt from a prefix's last window)"
            ),
            "speculative": (
                "speculative decoding cannot serve a model with window rings: a rejected draft's keys have already "
                "overwritten the ring positions of the tokens the window still needs"
            ),
            "int8_weights": (
                "int8 weight quantization is not implemented for the hybrid family's mixers and experts; "
                "serve this model with quantization='none'"
            ),
            "int8_pages": "quantized pages are not implemented for the window layers' rings; serve this model with kv_quantization='none'",
            "sharded": "a model with window rings serves on one chip a replica: its mixer and its rings are not sharded",
        }
    return {
        "reason": "latent_pages",
        "prefix_cache": f"a hit on cached latent pages {_NO_LATENT_SUFFIX}",
        "speculative": f"speculative decoding cannot serve a latent-attention model: verification {_NO_LATENT_SUFFIX}",
        "int8_weights": (
            "int8 weight quantization is not implemented for the hybrid family's mixers and experts; "
            "serve this model with quantization='none'"
        ),
        "int8_pages": "quantized latent pages are not implemented; serve this model with kv_quantization='none'",
        "sharded": "a latent-attention model serves on one chip a replica: its mixer and its pages are not sharded",
    }


def forward_decode_paged(
    params: dict,
    cfg: HybridConfig,
    ids: jax.Array,  # [S] current tokens
    positions: jax.Array,  # [S] positions of these tokens
    cache: dict,  # k/v pages of the attention layers + the slot state
    page_table: jax.Array,  # [S, wp]
    *,
    page_size: int,
    active: jax.Array,  # [S] bool: slots whose token is really consumed
    use_kernel: bool = True,
) -> tuple[jax.Array, dict]:
    """One incremental step for all S slots. The attention layers write the
    token's K and V into its page row and read the slot's pages as
    ``qwen.forward_decode_paged`` does (the Pallas kernel over lane-padded
    heads, or the gather path); a latent-attention layer writes the token's
    ONE latent row and reads the slot's pages in the absorbed form
    (ops/paged_latent_attention.py, or the gather path); the Mamba and short-conv layers advance the
    recurrent state of the ``active`` slots only: an ended, parked or held
    slot's state is what it was, bit for bit. ``use_kernel`` also puts the
    Mamba recurrence on its Pallas kernel (ops/ssm_state_update.py), which
    does not even read the state of a slot that is not live, and, on a TPU
    where a full batch gives an expert a handful of rows
    (``moe.takes_touched_form``), the expert matmuls on the launch that reads
    the touched experts only (ops/moe_touched_experts.py).

    Where ``cache`` carries COUNT_LEAVES (a decode chunk puts them there for
    its own length), every expert layer adds to them what its experts got
    from the ``active`` slots; a slot that is not active counts as no load."""
    from areal_tpu.inference import paged_kv

    S = ids.shape[0]
    H = cfg.num_heads
    lengths = (positions + 1).astype(jnp.int32)
    slot = jnp.arange(S)
    write_page = page_table[slot, positions // page_size]
    write_off = positions % page_size
    kv_quant = "k_scale" in cache
    not_pages = paged_kv.STATE_LEAVES + paged_kv.RING_LEAVES + COUNT_LEAVES
    fetch = None  # what the attention launches' work list fetches, where it names a block several slots hold once
    if use_kernel:
        from areal_tpu.ops.paged_attention_q8 import DecodeItems, decode_schedule, live_order, paged_attention_stacked, shared_decode_schedule

        attn_lengths = jnp.where(page_table[:, 0] == 0, 0, lengths)  # see qwen.forward_decode_paged
        ppcb = paged_kv.choose_ppcb(page_table.shape[1])
        if cfg.count("mla"):
            from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked
        if cfg.index_topk:
            # the index's launch scores every block of a slot's row for that slot alone, and the latent launch reads
            # under the selection it makes: a shared block would need every reader's mask rows, so the same list
            schedule = decode_schedule(attn_lengths, page_table.shape[1], page_size, ppcb)
            latent_items = DecodeItems.private(schedule)
        else:
            # K and V pages, or latent rows: each distinct block once, with the slots whose rows name it
            # (qwen.forward_decode_paged)
            with jax.named_scope("attn"):
                schedule, fetch = shared_decode_schedule(attn_lengths, page_table, page_size, ppcb)
                cache = fetch.counted(cache)
            latent_items = schedule
        # the state kernel's work list, made once a step
        state_launch = cfg.count("mamba") or cfg.count("gdn") or (cfg.count("kda") and kda_takes_launch(cfg))
        live = live_order(active) if state_launch else None
        kernel = dict(pages_per_compute_block=ppcb, schedule=schedule)
        with jax.named_scope("kv_write"):
            kv_live = live_order(page_table[:, 0] != 0)  # the KV writer's: qwen.forward_decode_paged
    else:
        live = kv_live = kernel = None
    if cfg.diff_attn or cfg.count("swa"):
        # the window layers' rings (paged_kv.RING_LEAVES): token t at ring position t % window, min(t + 1, window) of
        # its positions valid, a slot that is not live neither written (its row goes to the block past the last slot)
        # nor read; the work lists are the same for every window layer, so they are made here, once a step
        depth_of = _depths(cfg)
        W, R = cfg.sliding_window, cfg.ring_pages(page_size)
        with jax.named_scope("attn_window"):
            ring_at = positions % max(W, 1)
            ring_page = jnp.where(active, slot * R + ring_at // page_size, S * R)
            ring_off = ring_at % page_size
            ring_len = jnp.where(active, jnp.minimum(lengths, W), 0)
            ring_tbl = paged_kv.ring_table(S, R)
            ring_kernel = ring_live = None
            if use_kernel and R:
                ring_ppcb = paged_kv.choose_ppcb(R)
                # a slot's ring is its own: the list that names every block for its slot alone, in the launch's form
                ring_items = DecodeItems.private(decode_schedule(ring_len, R, page_size, ring_ppcb))
                ring_kernel = dict(pages_per_compute_block=ring_ppcb, schedule=ring_items)
                ring_live = live_order(active)

        def diff_read(q, k_pool, v_pool, j, lens, table, kern):
            """Both softmaxes of every differential head over the cached rows [k1 | k2], [v1 | v2]: [S, H, 2 hd]
            float32 (the queries go in float32: the two reads are subtracted, and nothing rounds them in between)."""
            qp = _diff_pack_q(q).astype(jnp.float32)
            if kern is not None:
                return paged_attention_stacked(qp, k_pool, v_pool, j, lens, table, sm_scale=cfg.sm_scale, **kern)
            k_j, v_j = (jax.lax.dynamic_index_in_dim(t, j, 0, keepdims=False) for t in (k_pool, v_pool))
            return paged_kv.paged_attention_xla(qp, k_j, v_j.astype(jnp.float32), lens, table, sm_scale=cfg.sm_scale)

        full_kernel = kernel if use_kernel else None
        full_len = attn_lengths if use_kernel else lengths
        n_live = jnp.sum(active, dtype=jnp.int32)
        # the cached tokens a read of the shared pages fetches: the DISTINCT ones where the launch's list names a
        # block several slots hold once, else every live slot's
        cached = jnp.sum(jnp.where(active, lengths, 0), dtype=jnp.int32) if fetch is None else fetch.tokens
        for leaf, scope, n in (
            ("shared_kv_tokens_read", "attn_cross", cached * (1 + cfg.count("cross"))),
            ("window_tokens_read", "attn_window", jnp.sum(ring_len, dtype=jnp.int32) * cfg.count("swa")),
            ("s6_updates", "ssm_state", n_live * cfg.count("s6")),
        ):
            if leaf in cache:
                with jax.named_scope(scope):
                    cache = {**cache, leaf: cache[leaf] + n}
    # the expert matmuls read the touched experts only where a full batch gives an expert a handful of rows
    # (a Pallas launch over the expert stacks; off a TPU, XLA's form whatever the shapes: moe.takes_touched_form)
    touched_form = (
        use_kernel and cfg.num_moe_layers > 0 and jax.default_backend() == "tpu"
        and moe.takes_touched_form(S, cfg.num_experts_per_tok, cfg.router_width, cfg.num_experts)
    )

    def step(kind, ffn, carry, layer, j, f):
        x, c = carry
        c = dict(c)
        x, streams = _block_in(cfg, layer, x)  # several residual streams: the mixer reads their pre-mix
        if kind == "mamba":
            h = _norm_in(cfg, layer, "input_norm", x)
            out, state = mamba_decode(cfg, layer, h, {k: c[k] for k in ("ssm", "conv")}, j, active, live)
            c.update(state)
        elif kind == "s6":
            with jax.named_scope("ssm_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, state, y = s6_decode(cfg, layer, h, {k: c[k] for k in ("ssm", "conv")}, j, active)
            c.update(state)
            if "gmu_m" in c:  # the LAST such layer's stays: this step's value, for the memory units further up
                c["gmu_m"] = y
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out = gmu_mix(cfg, layer, h, c["gmu_m"])
        elif kind == "swa" and not cfg.diff_attn:
            # plain grouped queries over the slot's ring, whose keys lie there rotated: read as they lie
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = _qkv(cfg, layer, h, positions, rotate=False, fence=True)
            if cfg.rotates("swa"):
                with jax.named_scope("attn_rope"):
                    q, k = _rotate_qk(cfg, q, k, positions)
            q, k, v = (_lane_pad(cfg, t) for t in (q, k, v))
            with jax.named_scope("kv_write"):
                pools = {n: paged_kv.ring_pool(c[n]) for n in paged_kv.RING_LEAVES}
                pools = paged_kv.write_decode_rows(pools, j, k, v, ring_page, ring_off, ring_live, pools=paged_kv.RING_LEAVES)
                c.update({n: pools[n].reshape(c[n].shape) for n in pools})
            with jax.named_scope("attn_window"):
                if ring_kernel is not None:
                    attn = paged_attention_stacked(q, pools["ring_k"], pools["ring_v"], j, ring_len, ring_tbl, sm_scale=cfg.sm_scale, **ring_kernel)
                else:
                    k_j, v_j = (jax.lax.dynamic_index_in_dim(pools[n], j, 0, keepdims=False) for n in paged_kv.RING_LEAVES)
                    attn = paged_kv.paged_attention_xla(q, k_j, v_j, ring_len, ring_tbl, sm_scale=cfg.sm_scale)
                # ... and ``W_o`` as [heads, head_dim, D] where the read's [S, heads, head_dim] meets it unflattened: a copy of
                # the layer's 134 MB every step
                attn = jax.lax.optimization_barrier(attn[..., : cfg.head_dim_].reshape(S, H * cfg.head_dim_).astype(x.dtype))
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        elif kind in ("swa", "cross") or (kind == "attention" and cfg.diff_attn):
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = _diff_qkv(cfg, layer, h, kv=kind != "cross")
            if kind == "swa":
                with jax.named_scope("kv_write"):
                    pools = {n: paged_kv.ring_pool(c[n]) for n in paged_kv.RING_LEAVES}
                    pools = paged_kv.write_decode_rows(pools, j, k, v, ring_page, ring_off, ring_live, pools=paged_kv.RING_LEAVES)
                    c.update({n: pools[n].reshape(c[n].shape) for n in pools})
                with jax.named_scope("attn_window"):
                    o = diff_read(q, pools["ring_k"], pools["ring_v"], j, ring_len, ring_tbl, ring_kernel)
            elif kind == "attention":
                with jax.named_scope("kv_write"):
                    c = paged_kv.write_decode_rows(c, j, k, v, write_page, write_off, kv_live)
                with jax.named_scope("attn"):
                    o = diff_read(q, c["k"], c["v"], j, full_len, page_table, full_kernel)
            else:  # the one full layer's pages, read again with this layer's own queries
                with jax.named_scope("attn_cross"):
                    o = diff_read(q, c["k"], c["v"], jnp.int32(0), full_len, page_table, full_kernel)
            o = _diff_merge(cfg, layer, o, depth_of[kind][j], x.dtype)
            with jax.named_scope("attn_proj"):
                out = _diff_out(cfg, layer, o)
        elif kind == "gdn":
            with jax.named_scope("gdn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, state = gdn_decode(cfg, layer, h, {k: c[k] for k in ("gdn", "conv")}, j, active, live)
            c.update(state)
            if "gdn_updates" in c:
                with jax.named_scope("gdn_state"):
                    c["gdn_updates"] = c["gdn_updates"].at[j].add(jnp.sum(active, dtype=jnp.int32))
        elif kind == "kda":
            with jax.named_scope("kda_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, state = kda_decode(cfg, layer, h, {k: c[k] for k in ("kda", "conv")}, j, active, live)
            c.update(state)
            if "kda_updates" in c:
                with jax.named_scope("kda_state"):
                    c["kda_updates"] = c["kda_updates"].at[j].add(jnp.sum(active, dtype=jnp.int32))
        elif kind == "conv":
            with jax.named_scope("conv_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
            out, c["conv"] = conv_decode(cfg, layer, h, c["conv"], j, active)
        elif kind == "mla":
            # the ABSORBED form: H query rows over ONE latent row a cached token
            with jax.named_scope("mla_proj"):
                # sliced HERE: XLA copies a layer's ``W_kvb`` out of the stack into fast memory, and names the copy after the slice
                layer = {**layer, "w_kvb": jax.lax.dynamic_index_in_dim(*layer["w_kvb"], 0, keepdims=False)}
                h = _norm_in(cfg, layer, "input_norm", x)
            q_nope, q_rope, lat, k_r, q_r = _mla_in(cfg, layer, h, positions)
            with jax.named_scope("mla_proj"):
                q = mla_absorbed_query(cfg, layer, q_nope, q_rope)
            more = None
            if cfg.index_topk:
                q_i, w_i = index_query(cfg, layer, h, q_r, positions)
                more = {"idx": index_key(cfg, layer, h, positions)[:, None, :]}
            with jax.named_scope("kv_write"):
                row = _latent_row(cfg, lat, k_r)
                c = paged_kv.write_decode_rows(c, j, row, None, write_page, write_off, kv_live, more=more)
            chosen = None
            if cfg.index_topk:
                # a slot without pages holds no cached token (its position is stale): nothing of it is scored or chosen
                chosen = index_select(cfg, q_i, w_i, c["idx"], j, attn_lengths if use_kernel else lengths, page_table, page_size, kernel)
            with jax.named_scope("attn"):
                if use_kernel:
                    o_lat = paged_latent_attention_stacked(
                        q, c["k"], j, attn_lengths, page_table, value_lanes=cfg.kv_lora_rank,
                        pages_per_compute_block=ppcb, schedule=latent_items, sm_scale=cfg.sm_scale, select=chosen,
                    )
                else:
                    pool = jax.lax.dynamic_index_in_dim(c["k"], j, 0, keepdims=False)
                    o_lat = paged_kv.paged_attention_xla(q, pool, pool, lengths, page_table, sm_scale=cfg.sm_scale, select=chosen)
                    o_lat = o_lat[..., : cfg.kv_lora_rank]
                if "latent_tokens_read" in c:
                    # every cached row of a live slot (under a selection the unselected are fetched and masked) ...
                    n_cached = jnp.sum(jnp.where(active, lengths, 0), dtype=jnp.int32)
                    # ... and the rows the read FETCHED: the distinct ones where the launch's list names a block several slots hold once
                    c["latent_tokens_read"] = c["latent_tokens_read"].at[j].add(n_cached if fetch is None else fetch.tokens)
                    if cfg.index_topk:
                        c["index_tokens_scored"] = c["index_tokens_scored"].at[j].add(n_cached)
                        c["latent_tokens_selected"] = c["latent_tokens_selected"].at[j].add(
                            jnp.sum(jnp.where(active[:, None], chosen, False), dtype=jnp.int32)
                        )
            with jax.named_scope("mla_proj"):
                out = _proj(cfg, layer, "wo", mla_absorbed_out(cfg, layer, o_lat.astype(x.dtype)))
        else:
            with jax.named_scope("attn_proj"):
                h = _norm_in(cfg, layer, "input_norm", x)
                q, k, v = (_lane_pad(cfg, t) for t in _qkv(cfg, layer, h, positions, fence=cfg.block_form == "parallel"))
            with jax.named_scope("kv_write"):
                c = paged_kv.write_decode_rows(c, j, k, v, write_page, write_off, kv_live)
            with jax.named_scope("attn"):
                if use_kernel:
                    attn = paged_attention_stacked(
                        q, c["k"], c["v"], j, attn_lengths, page_table,
                        pages_per_compute_block=ppcb, schedule=schedule,
                        k_scales=c.get("k_scale"), v_scales=c.get("v_scale"),
                        sm_scale=cfg.sm_scale,
                    )
                else:
                    sl = {
                        name: jax.lax.dynamic_index_in_dim(c[name], j, 0, keepdims=False)
                        for name in c
                        if name not in not_pages
                    }
                    scales = dict(k_scales=sl["k_scale"], v_scales=sl["v_scale"]) if kv_quant else {}
                    attn = paged_kv.paged_attention_xla(
                        q, sl["k"], sl["v"], lengths, page_table, sm_scale=cfg.sm_scale, **scales
                    )
                attn = attn[..., : cfg.head_dim_].reshape(S, H * cfg.head_dim_).astype(x.dtype)
            attn = _attn_gated(cfg, layer, attn, h)
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        with jax.named_scope(_MIXER_SCOPE[kind]):
            out = _norm_out(cfg, layer, "input_norm", out)
        x, load = _block_ffn(cfg, ffn, layer, x, out, h, active, streams)
        if "mhc_row_sublayers" in c:  # the mixer's and the FFN's mix, of every live row
            with jax.named_scope("mhc_post"):
                c["mhc_row_sublayers"] = c["mhc_row_sublayers"] + 2 * jnp.sum(active, dtype=jnp.int32)
        if load is not None and "moe_load" in c:
            with jax.named_scope("moe_router"):
                c["moe_load"] = c["moe_load"].at[f].add(load)
                held = load[cfg.expert_first : cfg.expert_first + cfg.num_experts]  # whose weights are here
                c["moe_touched"] = c["moe_touched"].at[f].add(jnp.sum(held > 0, dtype=jnp.int32))
        return x, c

    x = _embed(params, cfg, ids)
    # the expert stacks for the launch that reads them where they lie; ``W_kvb`` to be sliced under its own scope
    whole = (moe.EXPERT_LEAVES if touched_form else ()) + ("w_kvb",)
    if cfg.count("gmu"):  # this step's scan output of the last selective-scan layer rides with the cache, and leaves it below
        cache = {**cache, "gmu_m": jnp.zeros((S, cfg.s6_d_inner), jnp.float32)}
    x, out_cache = _scan_layers(cfg, params, (x, dict(cache)), step, whole=whole)
    out_cache.pop("gmu_m", None)
    if "moe_streamed" in cache:  # the experts whose weights this step read: the touched ones, or every one held
        with jax.named_scope("moe_router"):
            read = out_cache["moe_touched"] - cache["moe_touched"] if touched_form else cfg.num_experts
            out_cache["moe_streamed"] = cache["moe_streamed"] + read
    with jax.named_scope("lm_head"):
        hidden = _final_norm(params, cfg, x)
    return hidden, out_cache
