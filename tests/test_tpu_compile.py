"""Compile the main-path Pallas kernels, and every served family's two
engine programs, for a DESCRIBED TPU v5e.

No chip is attached: the TPU compiler that ships with jaxlib compiles for a
topology description (rehearsal 3 of the on-chip-measurement guide), and
raises exactly what the chip's compiler would raise — a slice not aligned
to the tiling, more VMEM than a kernel may use, a block shape the lowering
refuses. Interpret mode sees none of these; every failure this file guards
against passed its interpret-mode tests first.

``CASES``: one launch each with ``interpret=False`` at a benchmark cell's
shapes (by default Qwen2.5-1.5B's: 12 heads / 2 KV heads / head_dim 128, 28
layers, 128-token pages), held to a ``tpu_custom_call`` in the compiled
module; about two seconds a case. ``FAMILIES``: a row a family of
``models/hybrid.py``, its decode step and its prefill at the cell's published
widths, slots, window and longest bucket, cut to ONE period of its layer
pattern (10-35 s a program); a new family adds a row. The Qwen path's decode
steps are compiled whole at 28 scanned layers (4 s).
"""

import functools
import math
import os
import re
import sys
import typing

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
from chipbench_util import CHIP, load_run  # noqa: E402

L, KH, H, HD, PSZ = 28, 2, 12, 128, 128
SLOTS, WP = 128, 16  # decode: 128 slots x 2048-token windows
N_PAGES = SLOTS * WP + 1


@pytest.fixture(scope="module")
def chip():
    """ShapeDtypeStruct factory placed on one described v5e chip; the
    persistent compile cache is off around the module (an entry compiled
    for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this jaxlib
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _decode(page_dtype, slots=SLOTS, kh=KH, g=H // KH, layers=L, sm_scale=None, n_pages=N_PAGES, wp=32, q_dtype=jnp.bfloat16):
    """The decode kernel as a benchmark cell launches it: a 32-page table
    (4096-token window), 4 pages a block; by default ``rollout-1.5b-grpo``'s
    128 slots x 2 KV heads x group 6."""
    from areal_tpu.ops.paged_attention_q8 import paged_attention_stacked

    quant = page_dtype != jnp.bfloat16

    def fn(q, k, v, li, lengths, pt, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return paged_attention_stacked(
            q, k, v, li, lengths, pt, pages_per_compute_block=4, sm_scale=sm_scale, **kw
        )

    def args(S):
        pages = S((layers, kh, n_pages, PSZ, HD), page_dtype)
        a = [
            S((slots, kh * g, HD), q_dtype), pages, pages, S((), jnp.int32),
            S((slots,), jnp.int32), S((slots, wp), jnp.int32),
        ]
        if quant:  # lane-major scales
            a += [S((layers, kh, n_pages, 1, PSZ), jnp.float32)] * 2
        return a

    return fn, args


def _suffix(B, A, page_dtype=jnp.bfloat16):
    """Suffix prefill (chain mask) and tree verify (tree mask) are ONE
    kernel: the mask is an operand, so the block size and the page dtype
    are what the compiler sees."""
    from areal_tpu.ops.paged_suffix_attention import paged_suffix_attention

    quant = page_dtype != jnp.bfloat16

    def fn(q, ks, vs, k, v, li, plens, pt, mask, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return paged_suffix_attention(
            q, ks, vs, k, v, li, plens, pt, mask, interpret=False, **kw
        )

    def args(S):
        pages = S((L, KH, N_PAGES, PSZ, HD), page_dtype)
        a = [
            S((A, B, H, HD), jnp.bfloat16), S((A, B, KH, HD), jnp.bfloat16),
            S((A, B, KH, HD), jnp.bfloat16), pages, pages, S((), jnp.int32),
            S((A,), jnp.int32), S((A, 8), jnp.int32), S((A, B, B), jnp.bool_),
        ]
        if quant:
            a += [S((L, KH, N_PAGES, 1, PSZ), jnp.float32)] * 2
        return a

    return fn, args


def _ssm_state(dtype):
    """The recurrent-state update of one Mamba-2 layer at the published sizes
    of ``rollout-granite-h-micro-grpo``: 36 layers x 64 slots of 64 heads x
    64 x 128, updated in place for the live slots."""
    from areal_tpu.ops.paged_attention_q8 import live_order
    from areal_tpu.ops.ssm_state_update import ssm_state_update_stacked

    def fn(ssm, li, x, b, c, dt, a, active):
        return ssm_state_update_stacked(ssm, li, x, b, c, dt, a, *live_order(active))

    def args(S):
        f32 = jnp.float32
        return [
            S((36, 64, 64, 64, 128), dtype), S((), jnp.int32), S((64, 64, 64), f32), S((64, 1, 128), f32),
            S((64, 1, 128), f32), S((64, 64), f32), S((64,), f32), S((64,), jnp.bool_),
        ]

    return fn, args


def _gdn_state(dtype):
    """The delta-rule state update of one gated-delta-rule layer at the
    published sizes of ``rollout-olmo-hybrid-7b-d16-grpo``: 12 layers x 64
    slots of 30 heads x 96 x 192, two heads a tile, updated in place for the
    live slots."""
    from areal_tpu.ops.gdn_state_update import gdn_state_update_stacked
    from areal_tpu.ops.paged_attention_q8 import live_order

    def fn(state, li, q, k, v, alpha, beta, active):
        return gdn_state_update_stacked(state, li, q, k, v, alpha, beta, *live_order(active))

    def args(S):
        f32 = jnp.float32
        return [
            S((12, 64, 15, 96, 384), dtype), S((), jnp.int32), S((64, 30, 96), f32), S((64, 30, 96), f32),
            S((64, 30, 192), f32), S((64, 30), f32), S((64, 30), f32), S((64,), jnp.bool_),
        ]

    return fn, args


def _kda_state(dtype):
    """The state update of one ``kda`` layer at the published sizes of
    ``rollout-solar-open2-ep16-d8-longctx-grpo``: 6 layers x 64 slots of 64
    heads x 128 x 128, a decay a key channel, updated in place for the live
    slots."""
    from areal_tpu.ops.kda_state_update import kda_state_update_stacked
    from areal_tpu.ops.paged_attention_q8 import live_order

    def fn(state, li, q, k, v, decay, beta, active):
        return kda_state_update_stacked(state, li, q, k, v, decay, beta, *live_order(active))

    def args(S):
        f32 = jnp.float32
        return [
            S((6, 64, 64, 128, 128), dtype), S((), jnp.int32), S((64, 64, 128), f32), S((64, 64, 128), f32),
            S((64, 64, 128), f32), S((64, 64, 128), f32), S((64, 64), f32), S((64,), jnp.bool_),
        ]

    return fn, args


def _kda_prompt_scan():
    """A block of 1,024 tokens of one ``kda`` layer's prompt pass at the
    published sizes (64 heads of 128 x 128, 16 chunks, the state carried in):
    ONE launch, the chunk matrices and the state in VMEM."""
    from areal_tpu.ops.kda_prompt_scan import kda_prompt_scan

    def args(S):
        f32 = jnp.float32
        return [S((1024, 64, 128), f32)] * 4 + [S((1024, 64), f32), S((), jnp.int32), S((64, 128, 128), f32)]

    return kda_prompt_scan, args


def _kv_write(page_dtype, slots=SLOTS, kh=KH, layers=L, pages=2340):
    """A decode step's KV rows of one layer written by the one launch, at a
    benchmark cell's pool and slots (default: ``rollout-1.5b-grpo``'s
    ``[28, 2, 2340, 128, 128]`` x 128); int8 pages bring their scale rows."""
    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_attention_q8 import live_order

    def fn(cache, li, k, v, page, off, live):
        return paged_kv.write_decode_rows(cache, li, k, v, page, off, live_order(live))

    def args(S):
        cache = {n: S((layers, kh, pages, PSZ, HD), page_dtype) for n in ("k", "v")}
        if page_dtype != jnp.bfloat16:
            cache.update({f"{n}_scale": S((layers, kh, pages, 1, PSZ), jnp.float32) for n in ("k", "v")})
        rows = S((slots, kh, HD), jnp.bfloat16)
        return [cache, S((), jnp.int32), rows, rows, S((slots,), jnp.int32), S((slots,), jnp.int32), S((slots,), jnp.bool_)]

    return fn, args


def _flash_fwd(G=1, L=2048):
    from areal_tpu.ops import attention

    def args(S):
        x = S((G, L, H, HD), jnp.bfloat16)
        return [x, x, x, S((G, L), jnp.int32)]

    return (lambda q, k, v, seg: attention.flash_fwd_pallas(q, k, v, seg)), args


def _flash_train_grad(G=1, L=2048):
    """``flash_train`` (ops/flash_kernels.py: forward, dK/dV, dQ with their
    scalar-prefetched segment ranges) at the tiles ``flash_tiles`` gives
    the row length."""
    from areal_tpu.ops import attention

    def fn(q, k, v, seg):
        return jax.grad(
            lambda q, k, v: attention.flash_train(q, k, v, attention.flash_mask(seg, HD))
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    return fn, _flash_fwd(G, L)[1]


def _tree(grad: bool):
    from areal_tpu.ops import tree_attention as ta

    n = 1024

    def fwd(q, k, v, words, block_any):
        return ta.tree_attention(q, k, v, words, block_any, False)

    def bwd(q, k, v, words, block_any):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, words, block_any).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    def args(S):
        x = S((n, H, HD), jnp.bfloat16)
        return [
            x, x, x, S((n // ta.WORD, n), jnp.uint32),
            S((n // ta.BLOCK, n // ta.BLOCK_K), jnp.int32),
        ]

    return (bwd if grad else fwd), args


def _vocab_blocks(slots=SLOTS, vocab=151936):
    """The sampler's two launches over a decode step's logits
    (ops/vocab_block_stats.py): every block's statistics, then each row's
    chosen block."""
    from areal_tpu.ops import vocab_block_stats as vbs

    def fn(scaled, block):
        return vbs.vocab_block_stats(scaled), vbs.vocab_block_pick(scaled, block)

    def args(S):
        return [S((slots, vocab), jnp.float32), S((slots,), jnp.int32)]

    return fn, args


def _gmm():
    from areal_tpu.models.moe import pinned_gmm

    gmm = pinned_gmm()

    def args(S):  # the TPU m tile of models/moe.py
        return [
            S((4096, 2048), jnp.bfloat16), S((8, 2048, 1024), jnp.bfloat16),
            S((8,), jnp.int32),
        ]

    return (lambda x, w, gs: gmm(x, w, gs, tiling=(128, 128, 128), interpret=False)), args


def _moe_touched(slots=64, experts=16, width=768, layers=47):
    """The touched-expert launch as cell 7's decode step makes it: 64 rows
    on the stacks of 47 layers x 16 held experts of [2048, 768] (two experts'
    matrices in VMEM: 18.9 MB, past the default scoped limit)."""
    from areal_tpu.ops.moe_touched_experts import touched_expert_ffn

    def args(S):
        return [
            S((slots, 2048), jnp.bfloat16), S((slots, experts), jnp.float32),
            S((layers, experts, 2048, width), jnp.bfloat16), S((layers, experts, 2048, width), jnp.bfloat16),
            S((layers, experts, width, 2048), jnp.bfloat16), S((), jnp.int32), S((experts,), jnp.int32), S((), jnp.int32),
        ]

    return touched_expert_ffn, args


def _mla_prefill(heads=64, L=16384, dn=192, dv=256, tq=512, tk=1024, masked=True):
    """One query block's launch of the prompt pass's latent attention as the
    long-context cell's 16k program makes it (64 heads of 192 + 64 | 256, the
    selection as a mask operand, 512 queries against key blocks of 1,024:
    ``hybrid.prefill_blocks``), or cell 7's (32 heads of 128 + 64 | 128,
    causal, one block of 1,024)."""
    from areal_tpu.ops.latent_prefill_attention import key_lanes, mla_prefill_flash

    dk = key_lanes(dn, 64)

    def fn(q, kv, k_r, block, *mask):
        return mla_prefill_flash(q, kv, k_r, block, *mask, heads=heads, block_k=tk, sm_scale=(dn + 64) ** -0.5, interpret=False)

    def args(S):
        out = [S((tq, heads * dk), jnp.bfloat16), S((L, heads * (dk + dv)), jnp.bfloat16), S((L, dk), jnp.bfloat16), S((), jnp.int32)]
        return out + ([S((tq, L), jnp.bool_)] if masked else [])

    return fn, args


CASES = {
    "paged_decode_bf16": lambda: _decode(jnp.bfloat16),
    "paged_decode_int8": lambda: _decode(jnp.int8),
    "paged_decode_fp8": lambda: _decode(jnp.float8_e4m3fn),
    # rollout-7b-d14-grpo: 64 slots x 4 KV heads x group 7, 14 layers
    "paged_decode_7b_bf16": lambda: _decode(jnp.bfloat16, 64, 4, 7, 14),
    "paged_decode_7b_int8": lambda: _decode(jnp.int8, 64, 4, 7, 14),
    # rollout-granite-h-micro-grpo: 64 slots x 8 KV heads x group 4 over 4
    # attention layers, heads of 64 zero-padded to 128 lanes, softmax scale
    # 1/64 from the configuration; int8 pages are its control
    "paged_decode_h64pad_bf16": lambda: _decode(jnp.bfloat16, 64, 8, 4, 4, sm_scale=1 / 64),
    "paged_decode_h64pad_int8": lambda: _decode(jnp.int8, 64, 8, 4, 4, sm_scale=1 / 64),
    # the decode step's KV write at the three rollout cells' pools and slots
    "paged_kv_write_bf16": lambda: _kv_write(jnp.bfloat16),
    "paged_kv_write_int8": lambda: _kv_write(jnp.int8),
    "paged_kv_write_7b_bf16": lambda: _kv_write(jnp.bfloat16, 64, 4, 14, 1170),
    "paged_kv_write_7b_int8": lambda: _kv_write(jnp.int8, 64, 4, 14, 1170),
    "paged_kv_write_h64pad_bf16": lambda: _kv_write(jnp.bfloat16, 64, 8, 4, 1280),
    "paged_kv_write_h64pad_int8": lambda: _kv_write(jnp.int8, 64, 8, 4, 1280),
    "ssm_state_update_f32": lambda: _ssm_state(jnp.float32),
    "ssm_state_update_bf16": lambda: _ssm_state(jnp.bfloat16),
    "gdn_state_update_f32": lambda: _gdn_state(jnp.float32),
    "gdn_state_update_bf16": lambda: _gdn_state(jnp.bfloat16),
    "kda_state_update_f32": lambda: _kda_state(jnp.float32),
    "kda_state_update_bf16": lambda: _kda_state(jnp.bfloat16),
    "kda_prompt_scan_1k": _kda_prompt_scan,
    # Olmo-Hybrid-7B's attention layers: 30 KV heads, a query group of 1, 4 layers, 64 slots x 4096-token windows
    "paged_decode_mha30_bf16": lambda: _decode(jnp.bfloat16, 64, 30, 1, 4, n_pages=490),
    "paged_decode_mha30_int8": lambda: _decode(jnp.int8, 64, 30, 1, 4, n_pages=490),
    "paged_kv_write_mha30_bf16": lambda: _kv_write(jnp.bfloat16, 64, 30, 4, 490),
    # Phi-4-mini-flash-reasoning's differential attention: float32 queries [q1 | 0] / [0 | q2], 40 heads of 128 lanes in
    # groups of 4 over 10 rows [k1 | k2]; the ONE full layer's pages under the cell's whole window (160 pages, 20,480
    # tokens), and the eight window layers' rings (65 blocks of 4 pages: a 4-page table a slot); the rings' writer
    "paged_decode_diff_shared_f32q": lambda: _decode(jnp.bfloat16, 64, 10, 4, 1, sm_scale=1 / 8, n_pages=5734, wp=160, q_dtype=jnp.float32),
    "paged_decode_diff_ring_f32q": lambda: _decode(jnp.bfloat16, 64, 10, 4, 8, sm_scale=1 / 8, n_pages=260, wp=4, q_dtype=jnp.float32),
    "paged_kv_write_diff_ring_bf16": lambda: _kv_write(jnp.bfloat16, 64, 10, 8, 260),
    # the engine's smallest and largest suffix buckets at max_seq_len 2048
    "suffix_prefill_B256": lambda: _suffix(256, 4),
    "suffix_prefill_B2048": lambda: _suffix(2048, 2),
    "suffix_prefill_B256_int8": lambda: _suffix(256, 4, jnp.int8),
    "suffix_prefill_B256_fp8": lambda: _suffix(256, 4, jnp.float8_e4m3fn),
    # spec-decode verify: every slot, a handful of tree nodes (padded to 16)
    "tree_verify_B9": lambda: _suffix(9, SLOTS),
    "flash_fwd_pallas": _flash_fwd,
    "flash_train_grad": _flash_train_grad,
    # train-1.5b-packed4k: 3 packed rows of 4096 a layer
    "flash_fwd_pallas_3x4096": lambda: _flash_fwd(3, 4096),
    "flash_train_grad_3x4096": lambda: _flash_train_grad(3, 4096),
    "tree_attention_fwd": lambda: _tree(False),
    "tree_attention_bwd": lambda: _tree(True),
    "megablox_gmm": _gmm,
    "moe_touched_experts_kanana2": _moe_touched,
    "mla_prefill_flash_glm5_16k": _mla_prefill,
    "mla_prefill_flash_glm5_8k_1024_queries": lambda: _mla_prefill(L=8192, tq=1024),
    "mla_prefill_flash_kanana2_1k": lambda: _mla_prefill(32, 1024, 128, 128, 1024, 1024, masked=False),
    # 32 experts of [2048, 1792] (lfm2's, which the shape rule leaves on XLA's form): 44 MB of buffers
    "moe_touched_experts_wide": lambda: _moe_touched(128, 32, 1792, 12),
    # the sampler over rollout-1.5b-grpo's logits, over a vocabulary that ends inside a lane tile (one chip's
    # share of Kanana-2's) and over fewer rows than a tile and fewer columns than a block
    "vocab_blocks_152k": _vocab_blocks,
    "vocab_blocks_19360": lambda: _vocab_blocks(64, 19360),
    "vocab_blocks_5x300": lambda: _vocab_blocks(5, 300),
}


@functools.lru_cache(maxsize=None)
def _compiled_text(chip, name):
    """The compiled module of a case, once a worker process whichever test asks."""
    fn, args = CASES[name]()
    return jax.jit(fn).lower(*args(chip)).compile().as_text()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    assert "tpu_custom_call" in _compiled_text(chip, name)


def _pallas_call(jaxpr):
    """The first ``pallas_call`` equation of a jaxpr, looked for inside its calls too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in eqn.params.values():
            found = _pallas_call(sub.jaxpr) if hasattr(sub, "jaxpr") else None
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("name", [f"{k}_state_update_{d}" for k in ("ssm", "gdn", "kda") for d in ("f32", "bf16")])
def test_state_kernel_is_launched_with_the_walks_ring(chip, name):
    """At its cell's shapes a recurrent state kernel holds the whole ring of
    ``ops/slot_walk.py`` (four buffers of one slot's state each, a DMA
    semaphore a buffer and direction) and asks for the VMEM that takes: a ring
    that fell back to fewer buffers, or a limit the ring does not fit, fails
    here and not in a cell."""
    from areal_tpu.ops import slot_walk

    fn, args = CASES[name]()
    shapes = args(chip)
    eqn = _pallas_call(jax.make_jaxpr(fn)(*shapes).jaxpr)
    scratch = [v.aval for v in eqn.params["jaxpr"].invars[-eqn.params["grid_mapping"].num_scratch_operands :]]
    ring, isem, osem = scratch[:3]
    state = shapes[0]
    assert ring.shape == (4, *state.shape[2:]) and ring.dtype == state.dtype and slot_walk.RING == 4 and slot_walk.AHEAD == 2
    assert isem.shape == osem.shape == (4,)
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    held = slot_walk.ring_bytes(ring.shape[1:], ring.dtype)
    assert held >= math.prod(ring.shape) * ring.dtype.itemsize and held + (8 << 20) <= limit <= 100 << 20, (held, limit)


# the repo's own kernels by the ``name=`` of their ``pl.pallas_call``: what a
# device trace shows, and what a reduction finds them by
KERNEL_NAMES = {
    "paged_decode_bf16": ("paged_decode_attn",),
    "paged_decode_7b_int8": ("paged_decode_attn",),
    "suffix_prefill_B256": ("paged_suffix_attn",),
    "flash_fwd_pallas": ("flash_fwd",),
    # the library's names for these tiles: what the ledger's breakdown lists
    "flash_train_grad_3x4096": (
        "flash_mha_fwd_block_q_1024_block_k_major_1024_block_k_1024",
        "flash_mha_bwd_dkv_block_q_major_1024_block_q_1024_block_k_major_1024_block_k_1024",
        "flash_mha_bwd_dq_block_q_major_512_block_k_major_512_block_k_512",
    ),
    "ssm_state_update_f32": ("ssm_state_update",),
    "gdn_state_update_f32": ("gdn_state_update",),
    "kda_state_update_f32": ("kda_state_update",),
    "kda_prompt_scan_1k": ("kda_prompt_scan",),
    "paged_kv_write_int8": ("paged_kv_write",),
    "moe_touched_experts_kanana2": ("moe_touched_experts",),
    "mla_prefill_flash_glm5_16k": ("mla_prefill_flash",),
    "tree_attention_bwd": ("tree_attn_fwd", "tree_attn_bwd_dq", "tree_attn_bwd_dkv"),
    "vocab_blocks_152k": ("vocab_block_stats", "vocab_block_pick"),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_carries_its_name_on_v5e(chip, name):
    text = _compiled_text(chip, name)
    for kernel in KERNEL_NAMES[name]:
        assert kernel in text, f"{kernel} not in the compiled {name}"


def _cell1_steps(chip, kv_quant, next_ids):
    """The chunk program's loop at ``rollout-1.5b-grpo``'s sizes compiled for
    the described chip (28 scanned layers, 128 slots, the 8 GB pool donated,
    two steps fed back through ``next_ids(logits, step)``), and the cache's
    shapes."""
    from areal_tpu.inference import paged_kv
    from areal_tpu.models import qwen

    cfg = qwen.ModelConfig(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960, num_layers=L, num_heads=H, num_kv_heads=KH,
        head_dim=HD, dtype="bfloat16", tie_word_embeddings=True, attention_bias=True,
    )

    def described(tree):
        return jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(lambda: qwen.init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(lambda: paged_kv.init_paged_cache(cfg, 2340, PSZ, quant=kv_quant)))

    def steps(params, cache, ids, pos, table):
        def step(carry, i):
            ids, pos, cache = carry
            hid, cache = qwen.forward_decode_paged(params, cfg, ids, pos, cache, table, page_size=PSZ, use_kernel=True)
            ids = next_ids(qwen.compute_logits(params, cfg, hid), i)
            return (ids, pos + 1, cache), ids

        (_, _, cache), toks = jax.lax.scan(step, (ids, pos, cache), jnp.arange(2))
        return cache, toks

    i32 = functools.partial(chip, dtype=jnp.int32)
    compiled = jax.jit(steps, donate_argnums=1).lower(params, cache, i32((SLOTS,)), i32((SLOTS,)), i32((SLOTS, 32))).compile()
    return compiled, cache


@pytest.mark.parametrize("kv_quant", [False, "int8"], ids=["bf16", "int8"])
def test_decode_steps_update_the_pool_in_place(chip, kv_quant):
    """Two greedy steps: both kernels are in the program by name, no scatter
    touches the pool, and the compiled program holds no pool-sized temporary
    or copy (the check PR 21 made for the all-heads scatter, which cost a
    temporary and two copies a layer)."""
    compiled, cache = _cell1_steps(chip, kv_quant, lambda logits, i: jnp.argmax(logits, -1).astype(jnp.int32))
    text = compiled.as_text()
    assert "paged_kv_write" in text and "paged_decode_attn" in text
    pool = f"[{L},{KH},2340,{PSZ},{HD}]"
    touched = [line for line in text.splitlines() if pool in line and (" scatter(" in line or " copy(" in line)]
    assert not touched, touched[:3]
    pool_bytes = max(x.size * x.dtype.itemsize for x in cache.values())
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8  # the [128, 151936] logits, not a pool


def test_sampled_decode_steps_read_the_logits_once(chip):
    """Two steps through the engine's own ``_sample_step`` as the rollout
    cells run it (temperature, no top-k / top-p, no greedy slot): the
    sampler's two launches are in the program by name, and no ``reshape``,
    ``copy`` or ``while`` holds an array of slots x vocabulary float32
    elements: until PR 46 a reshape of the logits to a divisor of the
    vocabulary and its relayout were two passes of 78 MB a step."""
    from areal_tpu.inference.decode_programs import _sample_step

    state = {"temp": jnp.full((SLOTS,), 0.7, jnp.float32), "greedy": jnp.zeros((SLOTS,), bool)}

    def sample(logits, i):
        return _sample_step(logits, jax.random.fold_in(jax.random.PRNGKey(0), i), state, False, False, True)[0]

    text = _cell1_steps(chip, False, sample)[0].as_text()
    assert "vocab_block_stats" in text and "vocab_block_pick" in text
    sized = re.compile(r"f32\[([0-9,]+)\]")
    whole = [
        line.strip()[:200]
        for line in text.splitlines()
        if re.search(r" (reshape|copy|while)\(", line)
        and any(np.prod([int(d) for d in dims.split(",")]) >= SLOTS * 151936 for dims in sized.findall(line))
    ]
    assert not whole, whole[:3]


def test_static_shape_rule_matches_the_compiler(chip):
    """``paged_kernel_ok`` is what sends an engine to the gather path: it
    must say no exactly where the chip's compiler does (head_dim 64, the
    Qwen2.5-0.5B shape; quantized pages under 128 tokens)."""
    from areal_tpu.ops.paged_attention_q8 import (
        paged_attention_stacked,
        paged_kernel_ok,
    )

    def compiles(hd, psz, page_dtype):
        quant = page_dtype != jnp.bfloat16
        pages = chip((2, KH, 65, psz, hd), page_dtype)
        a = [
            chip((8, H, hd), jnp.bfloat16), pages, pages, chip((), jnp.int32),
            chip((8,), jnp.int32), chip((8, 8), jnp.int32),
        ]
        if quant:
            a += [chip((2, KH, 65, 1, psz), jnp.float32)] * 2

        def fn(q, k, v, li, le, pt, *sc):
            kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
            return paged_attention_stacked(
                q, k, v, li, le, pt, pages_per_compute_block=4, **kw
            )

        try:
            jax.jit(fn).lower(*a).compile()
            return True
        except Exception:  # noqa: BLE001 — Mosaic's refusal is the answer
            return False

    for hd, psz, dt in (
        (128, 16, jnp.bfloat16),
        (64, 128, jnp.bfloat16),
        (128, 64, jnp.int8),
        (128, 256, jnp.int8),
    ):
        assert compiles(hd, psz, dt) == paged_kernel_ok(
            hd, psz, dt != jnp.bfloat16
        ), (hd, psz, dt)


def _window_relayouts(text, taps):
    """The compiled ops that lay a bfloat16 window out again: a ``reshape``
    or ``copy`` whose result has the tap count (or the window's K-1) among
    its dimensions."""
    found = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[([\d,]+)\]\S* (reshape|copy)\(", ln)
        if m and {taps, taps - 1} & {int(d) for d in m.group(1).split(",")}:
            found.append(ln.strip()[:120])
    return found


@pytest.mark.parametrize("layers,channels", [(36, 4352), (12, 11520)], ids=["granite", "olmo"])
def test_conv_window_step_stays_in_the_rows_tiling(chip, layers, channels):
    """A decode step's conv window under a scan over layers at the shapes
    of ``rollout-granite-h-micro-grpo`` and ``rollout-olmo-hybrid-7b-d16-grpo``
    (64 slots x 3 taps x 4,352 and 11,520 channels, bfloat16): the row stays
    ``[64, 3 * C]`` in the tiling the state is stored in. A window shaped
    ``[slots, taps, channels]`` puts 3 into the second-minor dimension, which
    the chip pads to its tile: a reshape, a copy of the new token and a
    reshape back a layer (PR 35: 1.0 ms of a 15.7 ms step); the idiom as it
    was, written out here, must trip the same check."""
    from areal_tpu.models import hybrid

    slots, taps = 64, 4

    def as_it_was(conv, raw, w, bias, active):
        window = jnp.concatenate([conv.reshape(slots, taps - 1, channels), raw[:, None, :].astype(conv.dtype)], axis=1)
        acc = jnp.sum(window.astype(jnp.float32) * w[None], axis=1) + bias
        return acc, jnp.where(active[:, None], window[:, 1:].reshape(slots, -1), conv)

    def program(step):
        def fn(conv_all, raw_all, w_all, bias_all, active):
            def body(carry, i):
                conv_all, total = carry
                conv = jax.lax.dynamic_index_in_dim(conv_all, i, 0, keepdims=False)
                acc, new = step(conv, raw_all[i], w_all[i], bias_all[i], active)
                conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, new, i, 0)
                return (conv_all, total + jax.nn.silu(acc).astype(jnp.bfloat16)), None

            return jax.lax.scan(body, (conv_all, jnp.zeros(raw_all.shape[1:], jnp.bfloat16)), jnp.arange(layers))[0]

        args = (
            chip((layers, slots, (taps - 1) * channels), jnp.bfloat16), chip((layers, slots, channels), jnp.bfloat16),
            chip((layers, taps, channels), jnp.float32), chip((layers, channels), jnp.float32), chip((slots,), jnp.bool_),
        )
        return jax.jit(fn, donate_argnums=(0,)).lower(*args).compile().as_text()

    assert not _window_relayouts(program(hybrid._conv_window_step), taps)
    assert _window_relayouts(program(as_it_was), taps)


@pytest.mark.parametrize(
    "pools",
    [{"k": (48, 1, 477, PSZ, 640)}, {"k": (14, 4, 1170, PSZ, HD), "v": (14, 4, 1170, PSZ, HD)}],
    ids=["latent-pool-3.75GB", "kv-pools-2x2.1GB"],
)
def test_page_copy_reserves_no_second_pool_on_v5e(chip, pools):
    """A group's page copy at the cells' real pool sizes (the latent cell's
    one pool of 3.75 GB, the 7B cell's two of 2.1 GB), 8 pairs: every leaf
    copied in place on the donated cache. As a gather and a scatter over the
    pool the program reserved a second pool for the single-head latent shape
    (3.75 GB of temporaries by this count; 0.2 MB for the K/V shapes), which
    the chip did not have beside the weights (PERF.md, PR 37: the first chip
    call)."""
    from areal_tpu.inference import paged_kv

    cache = {name: chip(shape, jnp.bfloat16) for name, shape in pools.items()}
    pairs = chip((8,), jnp.int32)
    compiled = jax.jit(paged_kv.copy_pages, donate_argnums=(0,)).lower(cache, pairs, pairs, pairs, pairs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


def _no_copy_of(text: str, *shapes: str):
    """No ``copy`` in the compiled text touches a leaf of these shapes."""
    for shape in shapes:
        assert not [ln for ln in text.splitlines() if " copy(" in ln and shape in ln], shape


def _no_result_shaped(text: str, shape: str):
    """No op's RESULT has this shape (a regex): no copy, dynamic-slice or fusion makes it."""
    made = re.compile(r"= " + shape + r"\S* (?!parameter|get-tuple-element)")
    assert not [ln for ln in text.splitlines() if made.search(ln)], shape


def _lfm2_decode(mcfg, cache, text, temp):
    """The expert layer's dense form at 128 rows, the paged kernels on padded
    heads, the load counts in the carry: no copy of a whole expert stack into
    another layout (XLA:TPU made one of 5 GB for the un-batched einsum)."""
    assert "paged_decode_attn" in text and "paged_kv_write" in text
    _no_copy_of(text, "bf16[1,32,2048,1792]")
    assert temp < 200e6
    # 128 rows x top-4 over 32 experts: 16 assignments an expert, every expert touched: XLA's matmuls, not the touched-expert launch
    assert "moe_touched_experts" not in text


def _lfm2_prefill(mcfg, cache, text, temp):
    """4 x 1024 tokens: the routed expert form (16k assignment rows through
    ``megablox.gmm`` at ``moe.gmm_tiles``, which the chip's compiler must
    accept inside its default scoped VMEM), the masked conv window, the
    state's slot writes and the KV scatter."""
    from areal_tpu.models import moe

    assert not moe.takes_dense_form(4 * 1024, 32)
    assert text.count("tpu_custom_call") >= 3  # the three grouped matmuls of the expert layer


def _olmo_decode(mcfg, cache, text, temp):
    """The delta-rule state kernel on the packed float32 state in place, the
    paged kernels at 30 KV heads and a query group of 1, the update counts in
    the carry."""
    assert cache["gdn"].shape == (3, 64, 15, 96, 384) and cache["gdn"].dtype == jnp.float32
    assert "gdn_state_update" in text and "paged_decode_attn" in text and "paged_kv_write" in text
    # the state is advanced where it lies: no second copy of the stacked state (3 x 64 x 2.2 MB = 425 MB here) among the temporaries
    assert temp < 200e6


def _olmo_prefill(mcfg, cache, text, temp):
    """4 x 1024 tokens: the chunked delta-rule scan one row at a time, the
    masked conv windows, the state's slot writes and the KV scatter, within
    the memory the cell leaves beside its weights."""
    assert temp < 1.5e9


def _kanana2_decode(mcfg, cache, text, temp):
    """The latent kernel and the row writer as custom calls on the stacked
    pool where it lies (no copy of the page pool, no layer slice of it), the
    absorbed products without a second copy of ``W_kvb``'s stack, the counts
    in the carry; the expert matmuls as the touched-expert launch on the
    expert STACKS (64 rows x top-6 over a router of 128: 3 assignments an
    expert), with no layer of them sliced or copied out for it (151 MB a
    layer)."""
    assert set(cache) == {"k"} and cache["k"].shape == (3, 1, 477, PSZ, 640)
    assert "paged_latent_attn" in text and "paged_kv_write" in text and "paged_decode_attn" not in text
    _no_copy_of(text, "bf16[3,1,477,128,640]", "bf16[1,477,128,640]")
    assert "moe_touched_experts" in text
    _no_result_shaped(text, r"bf16\[(2,|1,)?16,(2048,768|768,2048)\]")  # a layer of an expert stack, or the stack
    # nor of W_q's stack, which XLA re-lays out whole where the projection's output is split without a barrier
    _no_copy_of(text, "bf16[2,2048,6144]")
    # XLA copies a layer's W_kvb out of the stack into fast memory and names the copy after the slice: made under the scope that reads it
    kvb = [ln for ln in text.splitlines() if "= bf16[1,512,8192]" in ln and "dynamic_slice" in ln]
    assert kvb and all("mla_proj/dynamic_slice" in ln for ln in kvb)
    assert temp < 200e6  # the pool is 234 MB here: nothing of its size among the temporaries


def _kanana2_prefill(mcfg, cache, text, temp):
    """2 x 1024 tokens in the plain form: per-head keys and values from the
    prompt's own latent one row at a time, the routed expert form over the
    held experts only (the router's other 112 sort past the grouped matmuls),
    the shared block, the latent rows' scatter."""
    from areal_tpu.models import hybrid, moe

    assert not moe.takes_dense_form(2 * 1024, 16) and moe.takes_dense_form(64, 16)
    assert text.count("tpu_custom_call") >= 4  # the three grouped matmuls of the expert layer, and the prompt pass's attention
    assert hybrid.prefill_takes_launch(mcfg, 1024) and "mla_prefill_flash" in text and "f32[32,1024,1024]" not in text  # no logits in HBM
    # W_kvb's stack of 47 layers is not laid out for the launch whole: a layer's slice is, once a layer
    _no_result_shaped(text, r"bf16\[\d+,512,12288\]")
    # the latent rows go into their pages layer by layer: no [layers, 2, 1024, 640] buffer, no pool-sized temporary
    assert temp < 0.4e9


def _glm5_decode(mcfg, cache, text, temp):
    """160 pages (20,480 tokens): the index's launch over the pool of index
    keys, the selection's 32 counting passes over [64, 20480], the latent
    launch under the selection, both pools written by one launch a layer, the
    expert matmuls as the touched-expert launch on the stacks (64 rows x top-8
    over 256: 2 assignments an expert) with an expert of [6144, 2048] going
    through the ring in 4 parts. No page pool copied, no layer of the expert
    stacks sliced out, the low-rank query's and the index's matrices not
    re-laid out whole, 0.25 GB of temporaries."""
    assert {k: v.shape for k, v in cache.items()} == {"k": (2, 1, 2730, PSZ, 640), "idx": (2, 1, 2730, PSZ, 128)}
    for name in ("paged_latent_attn", "paged_index_scores", "paged_kv_write", "moe_touched_experts"):
        assert name in text, name
    _no_copy_of(text, "bf16[2,1,2730,128,640]", "bf16[2,1,2730,128,128]", "bf16[1,2730,128,640]", "bf16[1,2730,128,128]")
    _no_result_shaped(text, r"bf16\[(1,)?16,(6144,2048|2048,6144)\]")  # a layer of an expert stack: 75 MB matrices
    # W_qb's and W^I_qb's stacks are not re-laid out whole (their outputs are split behind a barrier)
    _no_copy_of(text, "bf16[2,2048,16384]", "bf16[1,2048,16384]", "bf16[1,2048,4096]")
    assert temp < 0.3e9


def _glm5_prefill(mcfg, cache, text, temp):
    """ONE prompt of 16,384 tokens into both pools: attention blocked over 512
    queries, ONE ``mla_prefill_flash`` launch a block over key blocks of 1,024
    (no [H, L, L]: that would be 69 GB; no [H, queries, keys] float32 logits
    in HBM either: the XLA loop's were 134 MB a step), the selection made a
    query block at a time and handed to the launch as a mask, every head's
    keys and values of the prompt made once a layer in the launch's lane
    layout (1.07 GB), the expert rows through the grouped matmuls 2,048 at a
    time ON THE STACK (a layer's three matrices copied out for them were 1.15
    GB; 16k rows gathered at once 1.6 GB). 2.31 GB of temporaries by this
    count, where the XLA loop's program counts 2.45 (which over-counts a
    donated program: the compiler's own for that one was 2.12 GB): with 9.46
    GB of weights and a pool of 3.22 GB, under 15 GB. Three layers: the last
    one's feed-forward block feeds no page and is compiled away, the middle
    one's stays."""
    from areal_tpu.models import hybrid, moe

    assert hybrid.prefill_blocks(mcfg, 16384) == (256, 2048) and hybrid.ffn_block_rows(mcfg, "moe", 16384) == 2048
    assert hybrid.prefill_takes_launch(mcfg, 16384) and hybrid.prefill_blocks(mcfg, 16384, launch=True) == (512, 1024)
    assert not moe.takes_dense_form(2048, 16)
    assert text.count("tpu_custom_call") >= 4  # the three grouped matmuls of the expert layer, and the prompt pass's attention
    assert "mla_prefill_flash" in text and "f32[64,256,2048]" not in text and "f32[64,512,1024]" not in text  # no logits in HBM
    _no_result_shaped(text, r"bf16\[(1,)?16,(6144,2048|2048,6144)\]")  # the grouped matmuls read the stacks where they lie
    assert "bf16[131072,6144]" not in text  # 16k rows x top-8 are never gathered at once
    # W_kvb's stack is not laid out for the launch whole: a layer's slice is, once a layer (29 MB)
    _no_result_shaped(text, r"bf16\[3,512,(28672|32768)\]")
    assert temp < 2.4e9  # under the XLA loop's 2.45


_PHI_POOLS = ("bf16[8,10,65,4,128,128]", "bf16[8,10,260,128,128]", "bf16[1,10,5734,128,128]", "f32[9,64,16,5120]")


def _phi4flash_decode(mcfg, cache, text, temp):
    """160 pages: ``paged_decode_attn`` over the rings (a 4-page table) and
    over the full layer's pages, by that layer and by the seven cross layers;
    ``paged_kv_write`` into the rings (the 6-axis leaf merged to the pools'
    own layout and back: no copy) and into the pages; the selective-scan
    state advanced by XLA in place. Five launches in the text (the 32 layers
    are four scan bodies), no pool, ring or state copied or re-laid out, 0.1
    GB of temporaries."""
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 10, 5734, PSZ, 128), "v": (1, 10, 5734, PSZ, 128), "ssm": (9, 64, 16, 5120), "conv": (9, 64, 3 * 5120),
        "ring_k": (8, 10, 65, 4, PSZ, 128), "ring_v": (8, 10, 65, 4, PSZ, 128),
    }
    assert text.count("tpu_custom_call") >= 5 and "paged_decode_attn" in text and "paged_kv_write" in text
    _no_copy_of(text, *_PHI_POOLS)
    assert temp < 0.1e9


def _phi4flash_prefill(mcfg, cache, text, temp):
    """ONE prompt of 16,384 tokens: 17 of the 32 layers and layer 17's K and V
    (no cross layer, no memory unit, no full attention: the decode step that
    follows is the prompt's one row of those), the selective scan a token a
    step over a carried [1, 16, 5120] (nothing of [16384, 5120, 16]: 5.4 GB),
    window attention a block of 512 queries against 1,024 keys (no [40,
    16384, 16384]), the rings written a head an update (a window over heads
    and lanes re-lays the carried ring out: 2 x 1.36 GB copied in and out of
    the loop). 1.93 GB of temporaries: with 7.70 GB of weights and 5.33 GB of
    cache, 14.96 of 15.75 GB. Two prompts of 4,096 are never batched
    (``prefill_row_bytes``)."""
    from areal_tpu.inference.decode_programs import _PREFILL_STREAM_BYTES
    from areal_tpu.models import hybrid

    assert hybrid.prefill_row_bytes(mcfg, 4096) > _PREFILL_STREAM_BYTES > hybrid.prefill_row_bytes(mcfg, 256) * 4
    assert hybrid.ffn_block_rows(mcfg, "dense", 16384) == 8192
    _no_copy_of(text, *_PHI_POOLS)
    assert "f32[16384,16,5120]" not in text and "f32[16384,5120,16]" not in text and "16384,16384" not in text
    assert temp < 2.1e9


def _solar_open2_decode(mcfg, cache, text, temp):
    """160 pages: the kda state advanced by the ``kda_state_update`` launch in
    place (no copy of the 0.8 GB leaf, nor of a layer of it), the attention
    layer on ``paged_decode_attn`` / ``paged_kv_write``, the expert matmuls as
    the touched-expert launch on the stacks (64 rows x top-8 over 320: 1.6
    assignments an expert; an expert of [4096, 1280] through the ring in 2
    parts), 0.3 GB of temporaries."""
    from areal_tpu.models import hybrid, moe
    from areal_tpu.ops.moe_touched_experts import width_parts

    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 8, 2816, PSZ, 128), "v": (1, 8, 2816, PSZ, 128), "kda": (3, 64, 64, 128, 128), "conv": (3, 64, 3 * 24576),
    }
    assert moe.takes_touched_form(64, 8, 320, 20) and width_parts(4096, 1280, 2) == 2 and hybrid.kda_takes_launch(mcfg)
    for name in ("kda_state_update", "paged_decode_attn", "paged_kv_write", "moe_touched_experts"):
        assert name in text, name
    _no_copy_of(text, "f32[3,64,64,128,128]", "f32[64,64,128,128]", "bf16[1,8,2816,128,128]", "bf16[3,64,73728]")
    _no_result_shaped(text, r"bf16\[(1,)?20,(4096,1280|1280,4096)\]")  # no layer of an expert stack sliced out
    assert temp < 0.3e9


def _solar_open2_prefill(mcfg, cache, text, temp):
    """ONE prompt of 16,384 tokens: the attention layer under ``flash_fwd`` a
    KV head at a time (no [64, 16384, 16384]: 68 GB; no K or V of 64
    replicated heads either), its gate a block of 2,048 rows at a time, the
    kda layers a block of 1,024 tokens at a time with the float32 state
    carried (nothing of [16384, 64, 128] float32), the expert rows through
    the grouped matmuls 4,096 at a time on the stacks. Under 2.5 GB of
    temporaries (2.36 by this count at the cell's two periods): with 7.80 GB
    of weights and 4.62 GB of cache, 14.8 of 15.75 GB."""
    from areal_tpu.inference.decode_programs import _PREFILL_STREAM_BYTES
    from areal_tpu.models import hybrid

    assert hybrid.gqa_prefill_launch(mcfg, 16384) and hybrid.gqa_prefill_launch(mcfg, 4096) and not hybrid.gqa_prefill_launch(mcfg, 1024)
    assert hybrid.prefill_row_bytes(mcfg, 256) > _PREFILL_STREAM_BYTES and hybrid.ffn_block_rows(mcfg, "moe", 16384) == 4096
    assert "flash_fwd" in text and "f32[64,16384,16384]" not in text
    # a block's scan is the launch: the XLA form's keys as each sub-block sees them and its chunk matrices are gone
    assert hybrid.kda_prefill_launch(mcfg, 16384) and "kda_prompt_scan" in text
    assert "f32[16,64,4,64,128]" not in text and "f32[16,64,64,64]" not in text
    assert "f32[16384,64,128]" not in text and "f32[16384,8192]" not in text  # neither the scan's inputs nor the gate over the whole prompt
    _no_copy_of(text, "f32[3,64,64,128,128]", "bf16[1,8,2816,128,128]")
    assert temp < 2.5e9


def _cohere2_moe_decode(mcfg, cache, text, temp):
    """160 pages: the window layers read their rings (32 pages a slot) and
    the full layer the page table through ``paged_decode_attn`` at a query
    group of 16, both written by ``paged_kv_write`` in place (no copy of the
    1.6 GB ring leaves, nor of a layer of them), the expert matmuls as the
    touched-expert launch on the stacks (64 rows x top-8 over 128: 4
    assignments an expert; an expert of [4096, 4096]), under 0.4 GB of
    temporaries."""
    from areal_tpu.models import moe

    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 8, 3072, PSZ, 128), "v": (1, 8, 3072, PSZ, 128), "ring_k": (3, 8, 65, 32, PSZ, 128), "ring_v": (3, 8, 65, 32, PSZ, 128),
    }
    assert moe.takes_touched_form(64, 8, 128, 8)
    for name in ("paged_decode_attn", "paged_kv_write", "moe_touched_experts"):
        assert name in text, name
    _no_copy_of(text, "bf16[3,8,65,32,128,128]", "bf16[3,8,2080,128,128]", "bf16[8,2080,128,128]", "bf16[1,8,3072,128,128]")
    _no_result_shaped(text, r"bf16\[(1,)?8,4096,4096\]")  # no layer of an expert stack sliced out
    assert temp < 0.4e9


def _cohere2_moe_prefill(mcfg, cache, text, temp):
    """ONE prompt of 16,384 tokens: the window layers inside their band under
    ``swa_prefill_flash`` (nothing of [128, 4096, 8192] float32: 17 GB a
    block of the XLA form; no query transposed), the full layer under
    ``flash_fwd`` a KV head at a time, the expert rows 4,096 at a time (the
    shared block's gate and up of 16k rows x 16,384 columns are 1 GB), the
    rings written a head at a time in place. Under 3.2 GB of temporaries:
    with 6.25 GB of weights and 4.88 GB of cache, 14.3 of 15.75 GB."""
    from areal_tpu.inference.decode_programs import _PREFILL_STREAM_BYTES
    from areal_tpu.models import hybrid

    assert hybrid.swa_prefill_launch(mcfg, 16384) and hybrid.gqa_prefill_launch(mcfg, 16384) and hybrid.swa_prefill_launch(mcfg, 4096)
    assert 2 * hybrid.prefill_row_bytes(mcfg, 256) > _PREFILL_STREAM_BYTES and hybrid.ffn_block_rows(mcfg, "moe", 16384) == 4096  # every prompt goes alone
    assert "swa_prefill_flash" in text and "flash_fwd" in text
    assert "f32[128,4096,8192]" not in text and "f32[128,16384,16384]" not in text and "16384,16384]" not in text.replace("bf16[1,16384,16384]", "").replace("bf16[16384,16384]", "")
    assert "bf16[16384,65536]" not in text  # the shared block's gate and up over the whole prompt
    _no_copy_of(text, "bf16[3,8,65,32,128,128]", "bf16[1,8,3072,128,128]")
    assert temp < 3.2e9


def _results_outside_fusions(text: str) -> list[str]:
    """The instructions of a compiled program's text that stand outside fused computations: what lives in HBM."""
    out, inside = [], None
    for ln in text.splitlines():
        if ln and not ln.startswith(" ") and ln.rstrip().endswith("{"):
            inside = (ln.split()[1] if ln.startswith("ENTRY") else ln.split()[0]).lstrip("%")
        elif inside and "fused" not in inside and re.match(r"^  \S+ = ", ln):
            out.append(ln)
    return out


def _xing4_decode(mcfg, cache, text, temp):
    """160 pages (20,480 tokens), the two dense layers and an expert layer
    (two scan bodies: a program's temporaries are one layer's): the latent
    launch over every cached token (no index), the pool written by one launch
    a layer, the expert matmuls as the touched-expert launch on the stacks (64
    rows x top-4 over 64: 4 assignments an expert) with an expert of [3584,
    1024] through the ring whole; the 20 Sinkhorn rounds of a sublayer a
    STATIC chain (no while loop but the layers' two scans and the steps'),
    the four streams [64, 14336] on the lanes. No page pool copied, no layer
    of the expert stacks sliced out."""
    layers = mcfg.num_layers
    assert {k: v.shape for k, v in cache.items()} == {"k": (layers, 1, 2949, PSZ, 640)}
    for name in ("paged_latent_attn", "paged_kv_write", "moe_touched_experts"):
        assert name in text, name
    _no_copy_of(text, f"bf16[{layers},1,2949,128,640]", "bf16[1,2949,128,640]")
    _no_result_shaped(text, r"bf16\[(1,)?16,3584,1024\]")  # (a down matrix has the shape of 16 query blocks of the attention output)
    assert text.count(" while(") == 3  # the steps' scan and the layers' two: the rounds are no device loop
    assert "bf16[64,14336]" in text  # the streams side by side on the lanes
    rounds = [ln for ln in _results_outside_fusions(text) if "mhc_sinkhorn" in ln and " fusion(" in ln]
    assert 0 < len(rounds) <= 4 * 8  # two scan bodies x two sublayers: a handful of launches each, not one a round
    assert temp < 0.3e9


def _xing4_prefill(mcfg, cache, text, temp):
    """ONE prompt of 16,384 tokens into the latent pool: four streams of 117
    MB each beside their successors, the coefficients' 24 columns a token
    with the batch on the lanes, attention blocked over 1,024 queries under
    ``mla_prefill_flash``, the expert rows 8,192 at a time on the stack.
    Under 4 GB of temporaries: with 4.45 GB of weights and a pool of 4.5 GiB,
    under 15 GB."""
    from areal_tpu.models import hybrid

    assert hybrid.prefill_takes_launch(mcfg, 16384) and hybrid.prefill_blocks(mcfg, 16384, launch=True) == (1024, 1024)
    assert hybrid.ffn_block_rows(mcfg, "moe", 16384) == 8192
    assert "mla_prefill_flash" in text and "bf16[1,16384,14336]" in text
    made = _results_outside_fusions(text)
    # no float32 copy of the streams and no stream axis next to the minor one (a bfloat16 axis of 4 is padded to 16) in HBM
    assert not [ln for ln in made if re.search(r"= f32\[(1,)?16384,14336\]|= \S+\[(1,)?16384,4,3584\]", ln)]
    # the Sinkhorn rounds' vectors keep the batch on the lanes: none is laid out [rows, 1] as the mixes read them
    rounds = [ln for ln in made if "mhc_sinkhorn" in ln and " fusion(" in ln]
    assert rounds and not [ln for ln in rounds if "f32[16384,1]" in ln.split(" fusion(")[0]]
    _no_result_shaped(text, r"bf16\[(1,)?16,3584,1024\]")  # (a down matrix has the shape of 16 query blocks of the attention output)
    assert temp < 4e9


class Family(typing.NamedTuple):
    """A family's two engine programs at its cell's published widths: what the
    cell's configuration is cut to so that tier-1 can hold the compile, the
    cell's pool, slots, window and longest bucket, and what the compiled
    programs are held to. The layers are scanned, a run of one kind a body (a
    program's temporaries are one layer's), so ``cut`` keeps ONE whole period
    of the layer pattern and every layer kind, no more."""

    util: str  # the module of tests/benchmark_harness that builds the configuration as the cell does (its family's ``not_the_programs`` dropped from ``assumed``)
    config: str  # benchmarks/chip/configs/<config>.json
    cut: dict  # the published keys the depth is cut by
    pages: int  # of the cell's pool
    slots: int
    window: int  # pages of the decode step's table
    prompts: tuple[int, int]  # the prefill's (rows, bucket)
    decode: typing.Callable  # (mcfg, cache, the compiled text, bytes of temporaries): the family's own assertions
    prefill: typing.Callable
    budget: tuple | None = None  # what the cell sizes that pool from: (GiB, KV layers, heads, lanes)
    prefill_cut: dict = {}  # where the prompt pass needs another depth than the decode step


FAMILIES = {
    # one layer of each kind it has: conv + dense FFN, attention + experts, conv + experts
    "lfm2": Family("chipbench_lfm2_util", "lfm2-8b-a1b-d14", dict(num_hidden_layers=3, num_dense_layers=1, layer_types=["conv", "full_attention", "conv"]), 2225, 128, 32, (4, 1024), _lfm2_decode, _lfm2_prefill),
    # one period: three delta-rule layers and one attention layer
    "olmo": Family("chipbench_olmo_util", "olmo-hybrid-7b-d16", dict(num_hidden_layers=4, layer_types=["linear_attention"] * 3 + ["full_attention"]), 1900, 64, 32, (4, 1024), _olmo_decode, _olmo_prefill),
    # the leading dense layer and two expert layers (one expert layer is no stack: the scan over it is folded away)
    "kanana2": Family("chipbench_kanana2_util", "kanana-2-30b-a3b-ep8", dict(num_hidden_layers=3), 477, 64, 32, (2, 1024), _kanana2_decode, _kanana2_prefill),
    # the leading dense layer and one expert layer; the prompt pass one more (its last layer's feed-forward block is compiled away)
    "glm5": Family("chipbench_glm5_util", "glm-5-ep16-d6", dict(num_hidden_layers=2), 2730, 64, 160, (1, 16384), _glm5_decode, _glm5_prefill, (3.0, 6, 1, 640), dict(num_hidden_layers=3)),
    # ALL 32 layers: the five kinds come in an order of their own, not in periods
    "phi4flash": Family("chipbench_phi4flash_util", "phi-4-mini-flash-reasoning", {}, 5734, 64, 160, (1, 16384), _phi4flash_decode, _phi4flash_prefill, (3.5, 1, 10, 128)),
    # one whole period G K K K of the cell's two
    "solar_open2": Family("chipbench_solar_open2_util", "solar-open2-250b-ep16-d8", dict(num_hidden_layers=4, gqa_layers=[0]), 2816, 64, 160, (1, 16384), _solar_open2_decode, _solar_open2_prefill, (2.75, 2, 8, 128)),
    # one whole period S S S F: the cell's four layers
    "cohere2_moe": Family("chipbench_cohere2_moe_util", "command-a-plus-ep16-d4", {}, 3072, 64, 160, (1, 16384), _cohere2_moe_decode, _cohere2_moe_prefill, (1.5, 1, 8, 128)),
    # the two dense layers and two expert layers of the cell's ten (one expert layer is no stack, and the prompt pass compiles its last
    # layer's feed-forward block away); the 16,384 prefill's 40 s: 14 the dense body, 24 the expert body, 14 of them the bucket over 4,096
    "xing4": Family("chipbench_xing4_util", "xing4.0-29b-a4b-ep4-d10", dict(num_hidden_layers=4), 2949, 64, 160, (1, 16384), _xing4_decode, _xing4_prefill, (4.5, 10, 1, 640)),
}


def _family(chip, monkeypatch, row: Family, cut: dict):
    """(the program's configuration of the row's file under ``cut``, its
    weights and its cache as shapes on the described chip)."""
    import importlib
    import json

    from areal_tpu.inference import paged_kv
    from areal_tpu.models import hybrid

    load_run()  # puts the benchmark's ``benchlib`` on the path: the util modules build a configuration through the cell's kind
    with open(os.path.join(CHIP, "configs", row.config + ".json")) as f:
        mcfg = importlib.import_module(row.util).model_config({**json.load(f), **cut}, "bfloat16")
    if row.budget:
        gib, kv_layers, heads, lanes = row.budget
        assert paged_kv.n_pages_for_budget(int(gib * 2**30), kv_layers, heads, PSZ, lanes, 2, pools=mcfg.kv_pools) == row.pages
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), mcfg))
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(mcfg, row.pages, PSZ, slots=row.slots))
    place = lambda tree: jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)  # noqa: E731
    # the kernels and gmm ask the platform whether to compile or interpret: the described chip is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return mcfg, place(params), place(cache)


@pytest.mark.parametrize("family", FAMILIES)
def test_decode_steps_compile_for_v5e_at_the_cells_window(chip, monkeypatch, family):
    """Two decode steps as the engine's chunk runs them, at the cell's slots
    and table, the counts in the carry, the cache donated."""
    from areal_tpu.models import hybrid

    row = FAMILIES[family]
    mcfg, params, cache = _family(chip, monkeypatch, row, row.cut)

    def two_steps(params, cache, pt, ids, pos, active):
        cache = {**cache, **{k: jnp.zeros(s, jnp.int32) for k, s in mcfg.count_shapes.items()}}

        def step(c, _):
            ids, pos, cache = c
            h, cache = hybrid.forward_decode_paged(params, mcfg, ids, pos, cache, pt, page_size=PSZ, active=active, use_kernel=True)
            return (jnp.argmax(hybrid.compute_logits(params, mcfg, h), -1).astype(jnp.int32), pos + 1, cache), None

        (ids, _, cache), _ = jax.lax.scan(step, (ids, pos, cache), None, length=2)
        return ids, cache

    i32 = lambda *s: chip(s, jnp.int32)  # noqa: E731
    S = row.slots
    compiled = jax.jit(two_steps, donate_argnums=(1,)).lower(params, cache, i32(S, row.window), i32(S), i32(S), chip((S,), jnp.bool_)).compile()
    row.decode(mcfg, cache, compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("family", FAMILIES)
def test_prefill_compiles_for_v5e_at_the_cells_longest_bucket(chip, monkeypatch, family):
    """The engine's prefill program over the cell's longest bucket (the
    first three cells: a batch of 1,024-token prompts), the cache donated."""
    from areal_tpu.models import hybrid

    row = FAMILIES[family]
    mcfg, params, cache = _family(chip, monkeypatch, row, {**row.cut, **row.prefill_cut})
    rows, bucket = row.prompts

    prefill = lambda params, cache, *rest: hybrid.prefill_into_cache(params, mcfg, cache, *rest, page_size=PSZ)  # noqa: E731
    i32 = lambda *s: chip(s, jnp.int32)  # noqa: E731
    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(params, cache, i32(rows, bucket), i32(rows), i32(rows * bucket // PSZ), i32(rows)).compile()
    row.prefill(mcfg, cache, compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes)


# The engine's OWN programs of a family of ``models/qwen.py`` whose decode step is a block pass (``block_length`` > 1):
# (the module of tests/benchmark_harness that builds the configuration as the cell does, the configuration's file, the
# depth it is cut to (the layers are one scanned body), the cell's pool, slots, context and passes a chunk, the prefill's
# (rows, bucket))
BLOCK_FAMILIES = {
    "sdar": ("chipbench_sdar_util", "sdar-30b-a3b-d7", dict(num_hidden_layers=2), 1755, 64, 4096, 32, (8, 1024)),
}


def _block_programs(chip, monkeypatch, family: str):
    """(the family's ``DecodePrograms`` at its cell's server shapes, the
    abstract weights, cache, slot state and rng on the described chip)."""
    import importlib
    import json

    from areal_tpu import models
    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference import paged_kv
    from areal_tpu.inference.decode_programs import DecodePrograms, slot_state
    from areal_tpu.parallel import mesh as mesh_lib
    from areal_tpu.utils import compile_cache

    util, config, cut, pages, slots, context, steps, _ = BLOCK_FAMILIES[family]
    load_run()
    with open(os.path.join(CHIP, "configs", config + ".json")) as f:
        mcfg = importlib.import_module(util).model_config({**json.load(f), **cut}, "bfloat16")
    scfg = ServerConfig(
        dtype="bfloat16", max_batch_size=slots, max_seq_len=context, page_size=PSZ, decode_steps_per_call=steps, attn_window_step=context, seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1), prefix_cache=PrefixCacheConfig(enabled=True),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' launches are compiled, as on the chip
    model = models.family_of(mcfg)
    progs = DecodePrograms(model, mcfg, scfg, mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[:1]), store=compile_cache.ProgramStore(None))
    assert progs.use_kernel and progs.block == mcfg.block_length > 1
    place = lambda tree: jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)  # noqa: E731
    shapes = (
        place(jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))),
        place(jax.eval_shape(lambda: paged_kv.init_paged_cache(mcfg, pages, PSZ, slots=slots))),
        place(jax.eval_shape(lambda: jax.tree.map(jnp.asarray, slot_state(slots, mcfg.block_length)))),
        place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    )
    return progs, mcfg, shapes


@pytest.mark.parametrize("family", BLOCK_FAMILIES)
def test_block_chunk_compiles_for_v5e_at_the_cells_slots_and_window(chip, monkeypatch, family):
    """The engine's chunk program of a block model: 32 passes over 64 slots x
    4 rows, the in-block Pallas launch over a window of 32 pages, the head and
    the sampler's launches over 256 rows of 151,936, a clean block's rows
    through the row writer; the cache and the slot state donated. The pool is
    written in place: no result of the program has a pool's shape (one scatter
    over every row and KV head copied both pools twice a pass, PERF.md PR 58)."""
    progs, mcfg, (params, cache, state, rng) = _block_programs(chip, monkeypatch, family)
    _, _, _, pages, slots, context, steps, _ = BLOCK_FAMILIES[family]
    key = ("chunk", steps, context // PSZ, False, False, False)
    assert key in progs.warm_keys()
    progs.chunk_fn(*key[1:])
    compiled = progs._fn_cache[key].lower(params, cache, chip((slots, context // PSZ), jnp.int32), state, rng, chip((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_suffix_attn" in text and "paged_kv_write" in text and "vocab_block" in text
    made = _results_outside_fusions(text)
    pool = rf"bf16\[{mcfg.num_layers},{mcfg.num_kv_heads},{pages},{PSZ},{mcfg.head_dim_}\]"
    assert not [ln for ln in made if re.search(rf"= {pool}\S* (copy|fusion|scatter)", ln)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9  # 256 rows of float32 logits are 156 MB


@pytest.mark.parametrize("family", BLOCK_FAMILIES)
def test_block_causal_prefill_compiles_for_v5e_at_the_cells_longest_bucket(chip, monkeypatch, family):
    """The engine's prefill program under the block-causal mask: 8 prompts of
    1,024 tokens, 8,192 rows through the grouped expert matmuls; beside 9.97 GB
    of weights and 3.22 GB of pages its temporaries must stay under 2 GB."""
    progs, _, (params, cache, _, _) = _block_programs(chip, monkeypatch, family)
    rows, bucket = BLOCK_FAMILIES[family][-1]
    progs.prefill_fn(rows, bucket)
    i32 = lambda *s: chip(s, jnp.int32)  # noqa: E731
    compiled = progs._fn_cache[("prefill", rows, bucket, False)].lower(params, cache, i32(rows, bucket), i32(rows), i32(rows * bucket // PSZ), i32(rows)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
