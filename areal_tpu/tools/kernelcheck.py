"""kernelcheck — standing kernel-vs-XLA parity harness over ops/ kernels.

Every Pallas kernel in ``areal_tpu/ops/`` (and each private jax kernel the
main path calls) registers a *case grid* here: the kernel and an
independent pure-XLA reference over a spread of shapes/dtypes/quantization
variants. ``python -m areal_tpu.tools.kernelcheck`` runs the whole grid in
interpret mode on the CPU and exits nonzero on any divergence — so a
kernel PR lands onto a standing differential harness instead of ad-hoc
parity tests, and a jax bump that changes kernel semantics (not just
signatures — PVT covers those) fails loudly in CI. ``--compiled`` runs the
same closures at Qwen2.5-1.5B head shapes with ``interpret=False`` on a
TPU: chip_smoke.py's ``kernels`` phase.

Registering a kernel:

    @register_kernel("my_kernel")
    def _cases(compiled: bool = False):
        yield {
            "case": "f32-basic",            # unique within the kernel
            "build": lambda: {...},         # seeded inputs, as a pytree
            "kernel": lambda inp: ...,      # the launch -> array
            "reference": lambda inp: ...,   # pure-XLA ground truth -> array
            "tol": 2e-2,                    # max |kernel - reference| allowed
        }

The harness jits ``build``, ``kernel`` and ``reference`` — three programs
a case, not one per eager op: on a TPU every tiny program is a compile of
a few tenths of a second, and a grid built from eager ops spent five
minutes compiling six hundred of them — then compares max-abs-diff
against the case tolerance and reports per-case PASS/FAIL. Inputs come
from seeds (``jax.random`` on the device; small host tables from seeded
numpy), so runs are reproducible. A case without ``build`` is two plain
zero-argument closures, run as they are.

CLI:
  --list            enumerate registered kernels and their case counts
  --kernel NAME     run one kernel's grid only
  --json            machine-readable report on stdout
  --compiled        the on-chip grid (needs a TPU)
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Callable, Dict, Iterator

import numpy as np

REGISTRY: Dict[str, Callable[..., "Iterator[dict]"]] = {}

# tolerance of every compiled (on-chip) case: bf16 operands, f32
# accumulation, outputs of magnitude <= 1 — a wrong mask, scale or page
# shows as 1e-1 and more
CHIP_TOL = 3e-2


def register_kernel(name: str) -> Callable:
    def deco(fn: Callable) -> Callable:
        REGISTRY[name] = fn
        return fn

    return deco


def _normal(seed: int, shape, dtype=None):
    """Seeded N(0, 1); traced inside a case's ``build`` (the chip-size pools
    are too large to build in numpy and ship over)."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype or jnp.float32)


# Every generator takes ``compiled``: False is the interpret-mode grid at
# small shapes (CPU tier-1), True the SAME closures at Qwen2.5-1.5B head
# shapes (12 heads / 2 KV heads / head_dim 128, 128-token pages) with
# interpret=False — what chip_smoke.py's ``kernels`` phase runs on the TPU.

# ---------------------------------------------------------------------------
# paged decode attention (ops/paged_attention_q8.py): bf16 / int8 / fp8
# pages, lane-major scales, stacked cache
# ---------------------------------------------------------------------------

_PAGED_CHIP = dict(S=32, KH=2, G=6, hd=128, psz=128, wp=16)


def _paged_build(S=4, KH=2, G=6, hd=128, psz=16, wp=4, layers=1, seed=0,
                 q_dtype=None, pages=None, alias=0):
    """build() for the decode cases: q, one stacked K/V pool (float, or
    quantized to ``pages`` with lane-major scales), ragged lengths with a
    full slot and an empty one, and a page table. ``alias``: every run of
    that many slots holds its first one's first ``wp // 2`` pages and
    tokens past them (a group's shared prompt pages: the launch fetches
    such a block once)."""
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv

    rng = np.random.default_rng(seed)
    N = S * wp + 1
    pt = 1 + np.arange(S * wp, dtype=np.int32).reshape(S, wp)
    lens = rng.integers(1, wp * psz + 1, S).astype(np.int32)
    lens[0], lens[-1] = wp * psz, 0
    for first in range(0, S - 1, alias) if alias else ():
        members = range(first, min(first + alias, S - 1))
        lens[members] = np.maximum(lens[members], wp // 2 * psz + 1)
        pt[members, : wp // 2] = pt[first, : wp // 2]

    def build():
        inp = {
            "q": _normal(seed, (S, KH * G, hd), q_dtype),
            "k": _normal(seed + 100, (layers, KH, N, psz, hd)),
            "v": _normal(seed + 200, (layers, KH, N, psz, hd)),
            "lengths": jnp.asarray(lens),
            "pt": jnp.asarray(pt),
        }
        if pages in (jnp.int8, jnp.float8_e4m3fn):
            inp["k"], inp["ks"] = paged_kv.quantize_pages(inp["k"], dtype=pages)
            inp["v"], inp["vs"] = paged_kv.quantize_pages(inp["v"], dtype=pages)
        elif pages is not None:
            inp["k"], inp["v"] = inp["k"].astype(pages), inp["v"].astype(pages)
        return inp

    return build


def _live(out, lengths):
    """An empty slot's row is unspecified (the kernel writes zeros, the
    gather path a uniform average): compare live slots only. The empty
    slot still rides in the batch — the kernel must step over it."""
    import jax.numpy as jnp

    return jnp.where(lengths[:, None, None] > 0, out, 0)


def _paged_reference(layer):
    from areal_tpu.inference import paged_kv

    def reference(inp):
        scales = (inp["ks"][layer], inp["vs"][layer]) if "ks" in inp else ()
        return _live(
            paged_kv.paged_attention_xla(
                inp["q"], inp["k"][layer], inp["v"][layer],
                inp["lengths"], inp["pt"], *scales,
            ),
            inp["lengths"],
        )

    return reference


@register_kernel("paged_attention_q8")
def _cases_paged_q8(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import paged_attention_q8

    if compiled:
        grid = [(dict(_PAGED_CHIP), "int8-1p5b-heads", 4)]
    else:
        grid = [
            (dict(S=4, KH=2, G=6), "int8-S4-gqa6", 2),
            (dict(S=2, KH=1, G=8), "int8-S2-mha8", 2),
        ]
    for shape, label, ppcb in grid:
        yield {
            "case": label,
            "build": _paged_build(seed=shape["S"], pages=jnp.int8, **shape),
            # takes RAW q (applies 1/sqrt(hd) internally)
            "kernel": lambda inp, ppcb=ppcb: _live(
                paged_attention_q8(
                    inp["q"], inp["k"][0], inp["ks"][0], inp["v"][0], inp["vs"][0],
                    inp["lengths"], inp["pt"],
                    pages_per_compute_block=ppcb,
                    interpret=not compiled,
                ),
                inp["lengths"],
            ),
            "reference": _paged_reference(0),
            "tol": 3e-2,
        }


@register_kernel("paged_attention_stacked")
def _cases_paged_stacked(compiled: bool = False) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_attention_q8 import paged_attention_stacked

    L = 3
    shape, ppcb = (_PAGED_CHIP, 4) if compiled else ({}, 2)
    q_dtype = jnp.bfloat16 if compiled else None  # the serving dtype

    def case(label, layer, pages, alias=0):
        def kernel(inp):
            scales = (
                dict(k_scales=inp["ks"], v_scales=inp["vs"]) if "ks" in inp else {}
            )
            return _live(
                paged_attention_stacked(
                    inp["q"], inp["k"], inp["v"], jnp.int32(layer),
                    inp["lengths"], inp["pt"],
                    pages_per_compute_block=ppcb,
                    interpret=not compiled, **scales,
                ),
                inp["lengths"],
            )

        return {
            "case": label,
            "build": _paged_build(
                layers=L, seed=7, q_dtype=q_dtype, pages=pages, alias=alias, **shape
            ),
            "kernel": kernel,
            "reference": _paged_reference(layer),
            "tol": 3e-2,
        }

    # bf16 stacked cache (no scales), first and last layer indices
    for layer in (0, L - 1):
        yield case(f"stacked-bf16-layer{layer}", layer, jnp.bfloat16)
    # quantized stacked cache with lane-major scales
    for layer in (1, L - 1):
        yield case(f"stacked-int8-layer{layer}", layer, jnp.int8)
    yield case("stacked-fp8-layer1", 1, jnp.float8_e4m3fn)
    # groups whose rows name the same first pages: a block of them is one item, its readers' queries stacked
    yield case("stacked-bf16-aliased-groups-of-3", 1, jnp.bfloat16, alias=3)
    yield case("stacked-int8-aliased-groups-of-8", 1, jnp.int8, alias=8)

    # differential attention's shape (models/hybrid.py ``_diff_pack_q``): float32 queries [q1 | 0] and [0 | q2] of a
    # differential head, 4 query heads to ONE row [k1 | k2] / [v1 | v2], over a window layer's ring of 4 pages a slot;
    # against the two softmaxes computed apart, each over its own half of the key lanes. On the chip at the published
    # widths: 64 slots x 10 rows of 128 lanes x groups of 4, softmax scale 1/8
    ring = dict(S=64, KH=10, G=4, hd=128, psz=128, wp=4) if compiled else dict(S=4, KH=3, G=4, hd=32, psz=16, wp=2)
    half, layer = ring["hd"] // 2, 1

    def packed(inp):
        own = (jnp.arange(ring["hd"])[None, :] // half) == (jnp.arange(ring["KH"] * ring["G"])[:, None] % 2)
        return jnp.where(own[None], inp["q"], 0.0)

    def two_softmaxes(inp):
        q, S, W = packed(inp), ring["S"], ring["wp"] * ring["psz"]
        k, v = (jnp.moveaxis(inp[n][layer][:, inp["pt"]], 0, 3).reshape(S, W, ring["KH"], ring["hd"]).astype(jnp.float32) for n in ("k", "v"))
        qg = q.reshape(S, ring["KH"], ring["G"] // 2, 2, ring["hd"])  # [slot, row, differential head of the row, (q1, q2), lanes]
        valid = (jnp.arange(W)[None, :] < inp["lengths"][:, None])[:, None, None, :]
        outs = []
        for s in (0, 1):
            lanes = slice(s * half, (s + 1) * half)
            logits = jnp.einsum("srad,swrd->sraw", qg[:, :, :, s, lanes], k[..., lanes]) * half**-0.5
            p = jax.nn.softmax(jnp.where(valid, logits, -1e30), axis=-1)
            outs.append(jnp.einsum("sraw,swre->srae", p, v))
        return _live(jnp.stack(outs, axis=3).reshape(S, -1, ring["hd"]), inp["lengths"])

    yield {
        "case": "stacked-bf16-diff-pairs-f32q-ring",
        "build": _paged_build(layers=2, seed=11, q_dtype=jnp.float32, pages=jnp.bfloat16, **ring),
        "kernel": lambda inp: _live(
            paged_attention_stacked(
                packed(inp), inp["k"], inp["v"], jnp.int32(layer), inp["lengths"], inp["pt"],
                pages_per_compute_block=ring["wp"] if compiled else 2, sm_scale=half**-0.5, interpret=not compiled,
            ),
            inp["lengths"],
        ),
        "reference": two_softmaxes,
        "tol": 3e-2,
    }


# ---------------------------------------------------------------------------
# latent (MLA) decode attention (ops/paged_latent_attention.py): ONE pool of
# rows [c | k_r | 0] that are keys over all lanes and values over the first
# ``value_lanes``, against the gather path on the same pool given twice
# ---------------------------------------------------------------------------


@register_kernel("paged_latent_attention")
def _cases_paged_latent(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked

    L = 3
    # the latent cell's launch (64 slots, 32 heads, 576 values in 640 lanes), or a size the interpreter holds
    S, H, lanes, value, psz, wp, ppcb = (64, 32, 640, 512, 128, 32, 4) if compiled else (5, 4, 256, 128, 8, 4, 2)
    N = S * wp + 1

    def case(label, layer, pages, seed=5, group=1):
        def build():
            rng = np.random.default_rng(seed)
            lengths = rng.integers(1, wp * psz + 1, S).astype(np.int32)
            lengths[0], lengths[-1] = 0, wp * psz  # a slot no item names, and a full window
            pt = 1 + rng.permutation(N - 1)[: S * wp].reshape(S, wp)
            if group > 1:  # slots 1.. in groups of samples of one prompt: the first member's first half of the row in every row
                lengths[1:-1] = rng.integers(wp // 2 * psz + 1, wp * psz + 1, S - 2)
                for b in range(1, S - 1):
                    pt[b, : wp // 2] = pt[1 + (b - 1) // group * group, : wp // 2]
            return {
                "q": _normal(seed, (S, H, lanes), pages),
                "pool": _normal(seed + 1, (L, 1, N, psz, lanes), pages),
                "lengths": jnp.asarray(lengths),
                "pt": jnp.asarray(pt, jnp.int32),
            }

        def kernel(inp):
            return _live(
                paged_latent_attention_stacked(
                    inp["q"], inp["pool"], jnp.int32(layer), inp["lengths"], inp["pt"], value_lanes=value,
                    pages_per_compute_block=ppcb, sm_scale=lanes**-0.5, interpret=not compiled,
                ),
                inp["lengths"],
            )

        def reference(inp):
            pool = inp["pool"][layer].astype(jnp.float32)
            out = paged_kv.paged_attention_xla(inp["q"].astype(jnp.float32), pool, pool, inp["lengths"], inp["pt"], sm_scale=lanes**-0.5)
            return _live(out[..., :value], inp["lengths"])

        # bfloat16 pages: the probabilities are rounded to the pages' type before they meet the values (2^-9 a term)
        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": 1e-5 if pages == jnp.float32 else 3e-2}

    for layer in (0, L - 1):
        yield case(f"latent-bf16-layer{layer}", layer, jnp.bfloat16)
    # groups that share the first half of a row: the launch fetches those blocks once (3 readers: a pass of 2 and a
    # pass of 1 beside an empty place at 32 heads; the compiled grid's 11: an item of 8 and one of 3)
    yield case("latent-bf16-groups", 1, jnp.bfloat16, group=11 if compiled else 3)
    if not compiled:
        yield case("latent-f32-layer1", 1, jnp.float32)


# ---------------------------------------------------------------------------
# a learned index over latent pages (ops/paged_latent_attention.py): the index's
# launch over the pool of index keys against the scores of the gathered window,
# and the latent launch under a selection (the MASKED sparse read) against the
# gather path under the same mask. Selections leave whole blocks of a slot
# without a chosen token: the running softmax must step over them
# ---------------------------------------------------------------------------


@register_kernel("paged_index_select")
def _cases_paged_index(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.models import hybrid
    from areal_tpu.ops.paged_latent_attention import paged_index_scores_stacked, paged_latent_attention_stacked

    L = 3
    # the long-context cell's launches (64 slots, 64 heads over 640 lanes, 32 index heads of 128, a 160-page table), or sizes the interpreter holds
    S, H, lanes, value, Hi, d, psz, wp, ppcb, topk = (64, 64, 640, 512, 32, 128, 128, 160, 4, 2048) if compiled else (5, 4, 256, 128, 3, 128, 8, 6, 2, 7)
    N = min(S * wp, 3000) + 1

    def build(pages, seed=7):
        rng = np.random.default_rng(seed)
        lengths = rng.integers(1, wp * psz + 1, S).astype(np.int32)
        lengths[0], lengths[-1] = 0, wp * psz  # a slot no item names, and a full window
        return {
            "q": _normal(seed, (S, H, lanes), pages),
            "pool": _normal(seed + 1, (L, 1, N, psz, lanes), pages),
            "idx": _normal(seed + 2, (L, 1, N, psz, d), pages),
            "q_i": _normal(seed + 3, (S, Hi, d), pages),
            "w_i": _normal(seed + 4, (S, Hi)),
            "lengths": jnp.asarray(lengths),
            "pt": jnp.asarray(1 + rng.integers(0, N - 1, (S, wp)), jnp.int32),
        }

    def cached(inp):
        return jnp.arange(wp * psz)[None, :] < inp["lengths"][:, None]

    def scores_of(inp, layer):
        keys = inp["idx"][layer, 0][inp["pt"]].reshape(S, wp * psz, d)
        return hybrid.index_scores(inp["q_i"].astype(jnp.float32), inp["w_i"], keys.astype(jnp.float32))

    def score_case(label, layer, pages):
        def kernel(inp):
            out = paged_index_scores_stacked(
                inp["q_i"], inp["w_i"], inp["idx"], jnp.int32(layer), inp["lengths"], inp["pt"], pages_per_compute_block=ppcb, interpret=not compiled
            )
            return jnp.where(cached(inp), out, 0)  # past a slot's length the launch leaves what was there

        def reference(inp):
            return jnp.where(cached(inp), scores_of(inp, layer), 0)

        # scores are sums of Hi x d products of N(0, 1): of order sqrt(Hi * d); bfloat16 operands are exact, the sum float32
        return {"case": label, "build": lambda: build(pages), "kernel": kernel, "reference": reference, "tol": 1e-3 if pages == jnp.float32 else 5e-2}

    def read_case(label, layer, pages, clustered=False):
        def chosen(inp):
            if clustered:  # the last topk tokens alone: every earlier block of a long slot holds no chosen token
                return cached(inp) & (jnp.arange(wp * psz)[None, :] >= inp["lengths"][:, None] - topk)
            return hybrid.select_top(scores_of(inp, layer), cached(inp), topk)

        def kernel(inp):
            out = paged_latent_attention_stacked(
                inp["q"], inp["pool"], jnp.int32(layer), inp["lengths"], inp["pt"], value_lanes=value,
                pages_per_compute_block=ppcb, sm_scale=lanes**-0.5, select=chosen(inp), interpret=not compiled,
            )
            return _live(out, inp["lengths"])

        def reference(inp):
            pool = inp["pool"][layer].astype(jnp.float32)
            out = paged_kv.paged_attention_xla(
                inp["q"].astype(jnp.float32), pool, pool, inp["lengths"], inp["pt"], sm_scale=lanes**-0.5, select=chosen(inp)
            )
            return _live(out[..., :value], inp["lengths"])

        return {"case": label, "build": lambda: build(pages), "kernel": kernel, "reference": reference, "tol": 1e-5 if pages == jnp.float32 else 3e-2}

    yield score_case("scores-bf16-layer0", 0, jnp.bfloat16)
    yield read_case("masked-bf16-layer2", L - 1, jnp.bfloat16)
    yield read_case("masked-bf16-last-tokens", 1, jnp.bfloat16, clustered=True)
    if not compiled:
        yield score_case("scores-f32-layer1", 1, jnp.float32)
        yield read_case("masked-f32-layer1", 1, jnp.float32)
        yield read_case("masked-f32-last-tokens", 0, jnp.float32, clustered=True)


# ---------------------------------------------------------------------------
# the prompt pass's window attention (ops/window_prefill_attention.py): grouped
# queries inside the band ``0 <= t - s < window`` under ONE launch whose grid
# is the band, against the plain mask over the full causal softmax in float32
# ---------------------------------------------------------------------------


@register_kernel("swa_prefill_flash")
def _cases_swa_prefill(compiled: bool = False) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.window_prefill_attention import swa_prefill_flash

    def case(label, A, L, H, KH, hd, window, edge, dtype, seed=21):
        scale = hd**-0.5

        def build():
            return {"q": _normal(seed, (A, L, H * hd), dtype), "k": _normal(seed + 1, (A, KH, L, hd), dtype), "v": _normal(seed + 2, (A, KH, L, hd), dtype)}

        def kernel(inp):
            return swa_prefill_flash(inp["q"], inp["k"], inp["v"], heads=H, window=window, sm_scale=scale, edge=edge, interpret=not compiled)

        def reference(inp):
            q = inp["q"].astype(jnp.float32).reshape(A, L, KH, H // KH, hd)
            k, v = inp["k"].astype(jnp.float32), inp["v"].astype(jnp.float32)
            behind = jnp.arange(L)[:, None] - jnp.arange(L)[None, :]
            seen = (behind >= 0) & (behind < window)

            def head(args):  # one row and KV head at a time: [group, L, L] logits
                qh, kh, vh = args
                p = jax.nn.softmax(jnp.where(seen[None], jnp.einsum("tgd,sd->gts", qh, kh) * scale, -1e30), axis=-1)
                return jnp.einsum("gts,sd->tgd", p, vh)

            rows = (jnp.moveaxis(q, 2, 1).reshape(A * KH, L, H // KH, hd), k.reshape(A * KH, L, hd), v.reshape(A * KH, L, hd))
            out = jax.lax.map(head, rows).reshape(A, KH, L, H // KH, hd)
            return jnp.moveaxis(out, 1, 2).reshape(A, L, H * hd)

        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": 1e-5 if dtype == jnp.float32 else CHIP_TOL}

    if compiled:  # the cell's launch: a query group of 16 at heads of 128, a window of 4,096 under tiles of 1,024 (five a query tile) and of 512
        yield case("32-heads-over-2-window-4096-8192", 1, 8192, 32, 2, 128, 4096, 1024, jnp.bfloat16)
        yield case("32-heads-over-2-window-4096-4096-tiles-512", 1, 4096, 32, 2, 128, 4096, 512, jnp.bfloat16)
        yield case("16-heads-over-1-prompt-under-the-window-2048", 1, 2048, 16, 1, 128, 4096, 1024, jnp.bfloat16)
        return
    yield case("group-4-window-16-tiles-8-f32", 2, 64, 8, 2, 16, 16, 8, jnp.float32)
    yield case("group-4-window-20-tiles-8-f32", 1, 64, 4, 1, 16, 20, 8, jnp.float32)
    yield case("prompt-under-the-window-f32", 1, 32, 4, 2, 16, 64, 8, jnp.float32)


# ---------------------------------------------------------------------------
# the prompt pass's latent attention (ops/latent_prefill_attention.py): one
# query block's launch over the key blocks up to its diagonal against the whole
# [H, queries, L] softmax in float32, causal and under a selection handed over
# as a mask operand (one that leaves key blocks without a pick among them)
# ---------------------------------------------------------------------------


@register_kernel("mla_prefill_flash")
def _cases_mla_prefill(compiled: bool = False) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.latent_prefill_attention import mla_prefill_flash, padded_w_kvb, prefill_operands

    rank = 64

    def case(label, H, L, dn, dv, tq, tk, block, picks, dtype, seed=9):
        dr, scale = 64, (dn + 64) ** -0.5
        pos = block * tq + jnp.arange(tq)

        def build():
            w = (rank**-0.5 * _normal(seed, (rank, H * (dn + dv)))).astype(dtype)
            inp = {
                "c": _normal(seed + 1, (L, rank), dtype), "w": w, "k_r": _normal(seed + 2, (L, dr), dtype),
                "qn": _normal(seed + 3, (tq, H, dn), dtype), "qr": _normal(seed + 4, (tq, H, dr), dtype),
            }
            seen = pos[:, None] >= jnp.arange(L)[None, :]
            if picks:  # about ``picks`` keys a query and its own position; with ``picks`` < 0 its own and key 0 alone
                draw = jax.random.uniform(jax.random.PRNGKey(seed + 5), (tq, L)) * (pos[:, None] + 1) < picks
                seen = (seen & draw) | (pos[:, None] == jnp.arange(L)[None, :]) | (jnp.arange(L)[None, :] == 0)
            return {**inp, "seen": seen}

        def kernel(inp):
            q, kr = prefill_operands(inp["qn"], inp["qr"], inp["k_r"])
            kv = inp["c"] @ padded_w_kvb(inp["w"], H, dn, dr)
            return mla_prefill_flash(q, kv, kr, jnp.int32(block), inp["seen"] if picks else None, heads=H, block_k=tk, sm_scale=scale, interpret=not compiled)

        def reference(inp):
            kv = (inp["c"] @ inp["w"]).reshape(L, H, dn + dv).astype(jnp.float32)  # rounded to the operands' type, as the launch reads it
            logits = jnp.einsum("thd,shd->hts", inp["qn"].astype(jnp.float32), kv[..., :dn])
            logits = logits + jnp.einsum("thd,sd->hts", inp["qr"].astype(jnp.float32), inp["k_r"].astype(jnp.float32))
            p = jax.nn.softmax(jnp.where(inp["seen"][None], logits * scale, -1e30), axis=-1)
            return jnp.einsum("hts,shd->thd", p, kv[..., dn:]).reshape(tq, H * dv)

        # bfloat16: the probabilities are rounded to the values' type before they meet them, and the output to it
        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": 1e-5 if dtype == jnp.float32 else CHIP_TOL}

    if compiled:  # the two cells' launches: 64 heads of 192 + 64 | 256 under a selection, 32 heads of 128 + 64 | 128 causal
        yield case("64-heads-selection-4096-block7", 64, 4096, 192, 256, 512, 1024, 7, 2048, jnp.bfloat16)
        yield case("64-heads-selection-4096-block2", 64, 4096, 192, 256, 512, 1024, 2, 2048, jnp.bfloat16)
        yield case("64-heads-sparse-picks-4096-block3", 64, 4096, 192, 256, 1024, 1024, 3, -1, jnp.bfloat16)
        yield case("32-heads-causal-1024", 32, 1024, 128, 128, 1024, 1024, 0, 0, jnp.bfloat16)
        yield case("32-heads-causal-2048-block1", 32, 2048, 128, 128, 1024, 1024, 1, 0, jnp.bfloat16)
        return
    yield case("selection-f32-last-block", 4, 512, 192, 256, 128, 128, 3, 48, jnp.float32)
    yield case("sparse-picks-f32-block2", 4, 512, 192, 256, 128, 128, 2, -1, jnp.float32)
    yield case("causal-f32-keys-wider", 4, 512, 128, 128, 128, 256, 1, 0, jnp.float32)
    yield case("selection-bf16-block1", 4, 512, 192, 256, 256, 128, 1, 48, jnp.bfloat16)
    yield case("causal-bf16-first-block", 4, 256, 128, 128, 128, 128, 0, 0, jnp.bfloat16)


# ---------------------------------------------------------------------------
# the expert FFN over the touched experts only (ops/moe_touched_experts.py)
# against a loop over the listed experts in float32, rounded to the rows' type
# where the dense form rounds. The skip is shown by POISON: every expert off the list, and every other layer, is NaN (XLA's
# every-expert form gives NaN there: gate 0 x NaN)
# ---------------------------------------------------------------------------


@register_kernel("moe_touched_experts")
def _cases_moe_touched(compiled: bool = False) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.moe_touched_experts import touched_expert_ffn

    # the latent cell's launch (64 slots, 16 held experts of [2048, 768]), or a size the interpreter holds
    L, E, D, F, T = (3, 16, 2048, 768, 64) if compiled else (3, 6, 128, 256, 12)

    def case(label, listed, dtype, layer=1, tol=1e-5):
        listed = list(listed)

        def build():
            on = jnp.zeros((L, E), bool).at[layer, jnp.asarray(listed, jnp.int32)].set(True)[:, :, None, None]
            rows_on = jnp.zeros((T, E), bool).at[:, jnp.asarray(listed, jnp.int32)].set(True)
            return {
                "x": _normal(3, (T, D), dtype),
                # a row chooses about half the listed experts; no other has a gate (outputs of magnitude <= 1)
                "gate": jnp.where(rows_on & (_normal(4, (T, E)) > 0), jnp.abs(_normal(5, (T, E))) / max(len(listed), 1), 0.0),
                "wg": jnp.where(on, _normal(6, (L, E, D, F), dtype) * D**-0.5, jnp.nan),
                "wu": jnp.where(on, _normal(7, (L, E, D, F), dtype) * D**-0.5, jnp.nan),
                "wd": jnp.where(on, _normal(8, (L, E, F, D), dtype) * F**-0.5, jnp.nan),
            }

        ids = jnp.asarray(listed + [0] * (E - len(listed)), jnp.int32)

        def kernel(inp):
            return touched_expert_ffn(
                inp["x"], inp["gate"], inp["wg"], inp["wu"], inp["wd"], jnp.int32(layer), ids, jnp.int32(len(listed)), interpret=not compiled
            )

        def reference(inp):
            x = inp["x"].astype(jnp.float32)
            rounded = lambda a: a.astype(dtype).astype(jnp.float32)  # noqa: E731
            out = jnp.zeros((T, D), jnp.float32)
            for e in listed:
                wg, wu, wd = (inp[k][layer, e].astype(jnp.float32) for k in ("wg", "wu", "wd"))
                out = out + rounded(jax.nn.silu(rounded(x @ wg)) * rounded(x @ wu) * inp["gate"][:, e : e + 1]) @ wd
            return out

        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": tol}

    if compiled:
        yield case("bf16-half-touched", [1, 2, 5, 7, 8, 11, 12, 15], jnp.bfloat16, tol=CHIP_TOL)
        yield case("bf16-all-touched", range(E), jnp.bfloat16, layer=2, tol=CHIP_TOL)
        yield case("bf16-none-touched", [], jnp.bfloat16, layer=0, tol=0.0)
        return
    yield case("f32-all-touched", range(E), jnp.float32)
    yield case("f32-one-touched", [2], jnp.float32, layer=0)
    yield case("f32-last-only", [E - 1], jnp.float32, layer=L - 1)
    yield case("f32-more-than-the-ring", [4, 0, 3, 5], jnp.float32)  # any order: the list is the kernel's to walk, not to sort
    yield case("f32-none-touched", [], jnp.float32, tol=0.0)
    # a product one bfloat16 step off where the two sides add in another order moves an output by 1e-3
    yield case("bf16-three-touched", [0, 3, 4], jnp.bfloat16, tol=5e-3)


@register_kernel("moe_touched_experts_parts")
def _cases_moe_touched_parts(compiled: bool = False) -> Iterator[dict]:
    """The touched-expert launch where an expert goes through the ring in
    parts of its width (``width_parts`` > 1: [6144, 2048] on the chip; off
    it the ring is shrunk so that [128, 512] goes in 2 or 4), against the
    same loop: a part is a smaller expert under the same gate."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import moe_touched_experts as mte

    L, E, D, F, T = (2, 16, 6144, 2048, 64) if compiled else (2, 5, 128, 512, 12)

    def case(label, listed, dtype, ring_bytes=None, tol=1e-5, layer=1):
        listed = list(listed)

        def build():
            on = jnp.zeros((L, E), bool).at[layer, jnp.asarray(listed, jnp.int32)].set(True)[:, :, None, None]
            rows_on = jnp.zeros((T, E), bool).at[:, jnp.asarray(listed, jnp.int32)].set(True)
            return {
                "x": _normal(3, (T, D), dtype),
                "gate": jnp.where(rows_on & (_normal(4, (T, E)) > 0), jnp.abs(_normal(5, (T, E))) / max(len(listed), 1), 0.0),
                "wg": jnp.where(on, _normal(6, (L, E, D, F), dtype) * D**-0.5, jnp.nan),
                "wu": jnp.where(on, _normal(7, (L, E, D, F), dtype) * D**-0.5, jnp.nan),
                "wd": jnp.where(on, _normal(8, (L, E, F, D), dtype) * F**-0.5, jnp.nan),
            }

        ids = jnp.asarray(listed + [0] * (E - len(listed)), jnp.int32)

        def kernel(inp):
            was = mte._RING_BYTES
            mte._RING_BYTES = ring_bytes or was
            try:
                assert mte.width_parts(D, F, jnp.dtype(dtype).itemsize) > 1
                return mte.touched_expert_ffn(
                    inp["x"], inp["gate"], inp["wg"], inp["wu"], inp["wd"], jnp.int32(layer), ids, jnp.int32(len(listed)), interpret=not compiled
                )
            finally:
                mte._RING_BYTES = was

        def reference(inp):
            x = inp["x"].astype(jnp.float32)
            rounded = lambda a: a.astype(dtype).astype(jnp.float32)  # noqa: E731
            out = jnp.zeros((T, D), jnp.float32)
            for e in listed:
                wg, wu, wd = (inp[k][layer, e].astype(jnp.float32) for k in ("wg", "wu", "wd"))
                out = out + rounded(jax.nn.silu(rounded(x @ wg)) * rounded(x @ wu) * inp["gate"][:, e : e + 1]) @ wd
            return out

        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": tol}

    if compiled:
        yield case("bf16-4-parts-half-touched", [1, 2, 5, 7, 8, 11, 12, 15], jnp.bfloat16, tol=CHIP_TOL)
        yield case("bf16-4-parts-none-touched", [], jnp.bfloat16, layer=0, tol=0.0)
        return
    one_part = 2 * 3 * 128 * 512 * 4  # the ring's bytes for one whole [128, 512] expert in float32, twice
    yield case("f32-2-parts-three-touched", [4, 0, 3], jnp.float32, ring_bytes=one_part // 2)
    yield case("f32-4-parts-all-touched", range(E), jnp.float32, ring_bytes=one_part // 4, layer=0)
    yield case("f32-4-parts-none-touched", [], jnp.float32, ring_bytes=one_part // 4, tol=0.0)


# ---------------------------------------------------------------------------
# a decode step's KV rows (ops/paged_kv_write.py): one launch over the live
# slots against the per-head scatters, BIT-EQUAL over the whole pool
# ---------------------------------------------------------------------------


@register_kernel("paged_kv_write")
def _cases_paged_kv_write(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_kv_write import paged_kv_write
    from areal_tpu.ops.paged_attention_q8 import live_order

    L = 3
    S, psz, hd = (64, 128, 128) if compiled else (6, 16, 128)
    N = S + 1

    def case(label, layer, pages, KH, live_share=0.5, seed=11):
        rng = np.random.default_rng(seed)
        live = rng.permutation(S) < round(live_share * S)
        page = np.where(live, 1 + rng.permutation(S), 0).astype(np.int32)  # a page of its own a live slot
        off = rng.integers(0, psz, S).astype(np.int32)
        off[:3] = (0, psz - 1, 7)
        quant = pages != jnp.bfloat16

        def build():
            cache = {"k": _normal(seed, (L, KH, N, psz, hd)), "v": _normal(seed + 1, (L, KH, N, psz, hd))}
            rows = {"k": _normal(seed + 2, (S, KH, hd)), "v": _normal(seed + 3, (S, KH, hd))}
            for n in ("k", "v"):
                if quant:
                    cache[n], cache[f"{n}_scale"] = paged_kv.quantize_pages(cache[n], dtype=pages)
                    rows[n], scale = paged_kv.quantize_kv(rows[n], dtype=pages)
                    rows[f"{n}_scale"] = scale[..., 0]
                else:
                    cache[n], rows[n] = cache[n].astype(pages), rows[n].astype(pages)
            return {
                "cache": cache, "rows": rows,
                "live": jnp.asarray(live), "page": jnp.asarray(page), "off": jnp.asarray(off),
            }

        def flat(cache):  # every page but the trash page, where the scatters send an ended slot's row
            return jnp.concatenate([cache[n][:, :, 1:].astype(jnp.float32).reshape(-1) for n in sorted(cache)])

        # two launches back to back, as a step's layers are: the first, on the
        # layer before and with the slots in another order, leaves other tiles
        # in the kernel's VMEM, so that one the second used before it had
        # landed would go back with the wrong rows
        before = (layer - 1) % L

        def kernel(inp):
            cache, rows = inp["cache"], inp["rows"]
            scales = tuple(n for n in sorted(cache) if n.endswith("_scale"))
            order, n_live = live_order(inp["live"])
            out = cache
            for li, first in ((before, True), (layer, False)):
                new_pages, new_scales = paged_kv_write(
                    (out["k"], out["v"]), (rows["k"], rows["v"]), jnp.int32(li), inp["page"], inp["off"],
                    jnp.roll(order, -n_live)[::-1] if first else order, n_live,  # live slots first in both
                    scales=tuple(out[n] for n in scales), scale_rows=tuple(rows[n] for n in scales),
                    interpret=not compiled,
                )
                out = dict(zip(("k", "v", *scales), (*new_pages, *new_scales)))
            # the trash page is as it was
            same_trash = jnp.stack([(out[n][:, :, 0] == cache[n][:, :, 0]).all() for n in out]).all()
            return flat(out) + jnp.where(same_trash, 0.0, jnp.inf)

        def reference(inp):
            out = dict(inp["cache"])
            for li in (before, layer):
                for n, new in inp["rows"].items():
                    for h in range(KH):
                        at = (li, h, inp["page"], 0, inp["off"]) if n.endswith("_scale") else (li, h, inp["page"], inp["off"])
                        out[n] = out[n].at[at].set(new[:, h])
            return flat(out)

        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": 0.0}

    for KH in (2, 4, 8):  # the three rollout cells
        yield case(f"bf16-kh{KH}-layer0", 0, jnp.bfloat16, KH)
    yield case(f"bf16-kh2-layer{L - 1}", L - 1, jnp.bfloat16, 2)
    yield case("bf16-kh2-all-live", 1, jnp.bfloat16, 2, live_share=1.0)
    yield case("bf16-kh2-none-live", 1, jnp.bfloat16, 2, live_share=0.0)
    yield case("int8-kh2-layer1", 1, jnp.int8, 2)
    yield case("int8-kh8-layer0", 0, jnp.int8, 8)
    yield case("fp8-kh4-layer1", 1, jnp.float8_e4m3fn, 4)


# ---------------------------------------------------------------------------
# paged suffix attention (ops/paged_suffix_attention.py): suffix-prefill
# (chain mask) + tree-verify (ancestor mask) over bf16/int8/fp8 pages
# ---------------------------------------------------------------------------


def _suffix_case(
    S=3, B=6, KH=2, G=2, hd=16, psz=4, wp=4, L=2, layer=1,
    mask="chain", pages="f32", lens="ragged", ppcb=None, seed=0,
    compiled=False,
):
    """Build one paged_suffix_attention parity case. Returns (params,
    build, kernel_fn, reference_fn); the params dict is what --case repro
    wants."""
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops import paged_suffix_attention as psa

    params = dict(S=S, B=B, KH=KH, G=G, hd=hd, psz=psz, wp=wp, L=L,
                  layer=layer, mask=mask, pages=pages, lens=lens,
                  ppcb=ppcb, seed=seed)
    rng = np.random.default_rng(seed)
    H = KH * G
    N = S * wp + 1
    act = jnp.bfloat16 if compiled else jnp.float32  # activations' dtype
    pt = 1 + np.arange(S * wp, dtype=np.int32).reshape(S, wp)
    W = wp * psz
    if lens == "ragged":
        # 0, full, and page-boundary-straddling lengths (NOT multiples of
        # psz or of the ppcb*psz block) in one batch
        pool = [0, W] + [int(x) for x in rng.integers(1, W, max(S, 2))]
        plens = np.asarray(pool[:S], np.int32)
    else:  # "aligned": page-multiple lengths (radix prefixes)
        plens = (psz * rng.integers(0, wp + 1, S)).astype(np.int32)
    if mask == "chain":
        seg = np.ones((S, B), np.int32)
        seg[:, B - 1] = 0  # one padded suffix row
        m = (
            np.tril(np.ones((B, B), bool))[None]
            & (seg[:, :, None] != 0)
            & (seg[:, None, :] != 0)
        )
    else:  # "tree": random parent-before-child ancestor-or-self mask
        m = np.zeros((S, B, B), bool)
        m[:, np.arange(B), np.arange(B)] = True
        m[:, :, 0] = True
        for s in range(S):
            for r in range(1, B):
                p = int(rng.integers(0, r))
                m[s, r] |= m[s, p]

    def build():
        inp = {
            "q": _normal(seed, (S, B, H, hd), act),
            "ks": _normal(seed + 100, (S, B, KH, hd), act),
            "vs": _normal(seed + 200, (S, B, KH, hd), act),
            "k": _normal(seed + 300, (L, KH, N, psz, hd)),
            "v": _normal(seed + 400, (L, KH, N, psz, hd)),
            "plens": jnp.asarray(plens),
            "pt": jnp.asarray(pt),
            "mask": jnp.asarray(m),
        }
        if pages in ("int8", "fp8"):
            dt = jnp.int8 if pages == "int8" else jnp.float8_e4m3fn
            inp["k"], inp["k_scales"] = paged_kv.quantize_pages(inp["k"], dtype=dt)
            inp["v"], inp["v_scales"] = paged_kv.quantize_pages(inp["v"], dtype=dt)
        elif pages == "bf16":
            inp["k"] = inp["k"].astype(jnp.bfloat16)
            inp["v"] = inp["v"].astype(jnp.bfloat16)
        return inp

    def call(fn, inp, **kw):
        scales = {k: inp[k] for k in ("k_scales", "v_scales") if k in inp}
        return fn(
            inp["q"], inp["ks"], inp["vs"], inp["k"], inp["v"], jnp.int32(layer),
            inp["plens"], inp["pt"], inp["mask"], **scales, **kw,
        )

    def kernel(inp):
        return call(
            psa.paged_suffix_attention, inp,
            pages_per_compute_block=ppcb, interpret=not compiled,
        )

    def reference(inp):
        return call(psa.paged_suffix_attention_xla, inp)

    return params, build, kernel, reference


@register_kernel("paged_suffix_attention")
def _cases_paged_suffix(compiled: bool = False) -> Iterator[dict]:
    if compiled:
        chip = dict(KH=2, G=6, hd=128, psz=128, wp=8, L=3, compiled=True)
        grid = [
            # the engine's smallest and largest suffix buckets at
            # max_seq_len 2048, and a verify-sized tree, at 1.5B heads
            ("chain-bf16-B256", dict(S=4, B=256, pages="bf16", seed=1)),
            ("chain-bf16-B2048", dict(S=2, B=2048, pages="bf16", seed=2)),
            ("chain-bf16-B300-padded",
             dict(S=2, B=300, pages="bf16", lens="aligned", seed=3)),
            ("tree-bf16-B9-verify",
             dict(S=32, B=9, mask="tree", pages="bf16", layer=0, seed=4)),
            ("chain-int8-B256", dict(S=4, B=256, pages="int8", seed=5)),
            ("tree-fp8-B16", dict(S=8, B=16, mask="tree", pages="fp8", seed=6)),
        ]
        grid = [(label, {**chip, **o}, CHIP_TOL) for label, o in grid]
    else:
        grid = [
            # (label, overrides, tol) — GQA ratios x ragged/aligned lengths
            # x bf16/int8/fp8 pages x chain/tree masks, page-straddling
            # blocks, and suffixes long enough to tile (several query
            # tiles: B*G > 512; several key steps: B > 512)
            ("chain-f32-gqa2-ragged", dict(), 2e-4),
            ("chain-bf16-mha1-aligned",
             dict(KH=4, G=1, pages="bf16", lens="aligned", seed=1), 2e-2),
            ("chain-f32-gqa4-straddle-ppcb2",
             dict(KH=1, G=4, wp=6, ppcb=2, seed=2), 2e-4),
            ("tree-f32-gqa2-ragged", dict(mask="tree", seed=3), 2e-4),
            ("tree-bf16-gqa2-layer0",
             dict(mask="tree", pages="bf16", layer=0, seed=4), 2e-2),
            ("chain-int8-gqa2-ragged", dict(pages="int8", seed=5), 2e-4),
            ("tree-int8-mha1-straddle",
             dict(mask="tree", pages="int8", KH=4, G=1, wp=6, ppcb=3, seed=6),
             2e-4),
            ("chain-fp8-gqa2-ragged", dict(pages="fp8", seed=7), 2e-4),
            ("tree-fp8-gqa4-aligned",
             dict(mask="tree", pages="fp8", KH=1, G=4, lens="aligned", seed=8),
             2e-4),
            ("chain-f32-gqa8-B128-qtiles",
             dict(S=2, B=128, KH=1, G=8, seed=9), 2e-4),
            ("tree-int8-gqa2-B640-ktiles",
             dict(S=1, B=640, KH=1, G=2, mask="tree", pages="int8", seed=10),
             2e-4),
        ]
    for label, overrides, tol in grid:
        params, build, kernel, reference = _suffix_case(**overrides)
        yield {
            "case": label,
            "params": params,
            "build": build,
            "kernel": kernel,
            "reference": reference,
            "tol": tol,
        }


# ---------------------------------------------------------------------------
# flash attention (ops/attention.py): the repo's forward kernel and the
# differentiable kernels behind flash_train (ops/flash_kernels.py)
# ---------------------------------------------------------------------------


def _packed_mask(seg_np):
    """The flash kernels' semantics as a dense mask: causal AND same
    segment AND seg != 0 -> [G, 1, L, L]."""
    L = seg_np.shape[-1]
    qi = np.arange(L)[:, None]
    ki = np.arange(L)[None, :]
    return (
        (qi >= ki)
        & (seg_np[:, :, None] == seg_np[:, None, :])
        & (seg_np[:, :, None] != 0)
    )[:, None]


def _out_and_grads(attn, q, k, v, w):
    """attn's output and its q/k/v gradients under the fixed cotangent
    ``w``, stacked into one array."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        out = attn(q, k, v).astype(jnp.float32)
        return (out * w).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    return jnp.stack([out, *(g.astype(jnp.float32) for g in grads)])


@register_kernel("flash_fwd")
def _cases_flash_fwd(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.ops import attention

    G, L, H, d = (1, 2048, 12, 128) if compiled else (1, 128, 2, 128)
    dt = jnp.bfloat16 if compiled else jnp.float32
    grids = {
        "one-segment": np.ones((G, L), np.int32),
        "packed-two-segments": np.concatenate(
            [np.ones((G, L // 2), np.int32), 2 * np.ones((G, L // 2), np.int32)],
            axis=1,
        ),
    }

    def build(seg_np):
        shape = (*seg_np.shape, H, d)
        return lambda: {
            "q": _normal(11, shape, dt),
            "k": _normal(12, shape, dt),
            "v": _normal(13, shape, dt),
            "w": _normal(23, shape),
            "seg": jnp.asarray(seg_np),
            "mask": jnp.asarray(_packed_mask(seg_np)),
        }

    for label, seg_np in grids.items():
        yield {
            "case": ("bf16-" if compiled else "f32-") + label,
            "build": build(seg_np),
            "kernel": lambda inp: attention.flash_fwd_pallas(
                inp["q"], inp["k"], inp["v"], inp["seg"], interpret=not compiled
            ),
            "reference": lambda inp: attention.sdpa_xla(
                inp["q"], inp["k"], inp["v"], inp["mask"], d
            ),
            "tol": CHIP_TOL if compiled else 2e-4,
        }


@register_kernel("flash_train")
def _cases_flash_train(compiled: bool = False) -> Iterator[dict]:
    """``flash_train`` (forward AND gradients, ops/flash_kernels.py)
    against XLA sdpa. Rows of padding (segment 0) carry no loss in the
    trainer: their output is zeroed on both sides, and with it what they
    send back."""
    import jax.numpy as jnp

    from areal_tpu.ops import attention

    H, d = (12, 128) if compiled else (2, 128)
    dt = jnp.bfloat16 if compiled else jnp.float32

    def segments(L, cuts):
        seg = np.zeros((1, L), np.int32)
        for i, (a, b) in enumerate(zip(cuts, cuts[1:])):
            seg[:, a:b] = i + 1
        return seg

    if compiled:
        # the train cell's row length, so its tiles (1024 and 512): five
        # segments whose boundaries fall inside tiles, then a padded tail
        grids = {
            "bf16-L2048-two-segments": (segments(2048, [0, 1024, 2048]), None),
            "bf16-L4096-five-segments-padded-tail": (segments(4096, [0, 700, 1500, 2300, 3000, 3900]), None),
        }
    else:
        # tiles of 128 on a row of 512, so that some are skipped: a boundary
        # inside a tile, one on a tile's edge, a padded tail
        edges = attention.FlashBlocks((128, 128), (128, 128), (128, 128))
        grids = {
            "f32-L512-three-segments-padded-tail": (segments(512, [0, 100, 256, 450]), edges),
            "f32-L512-one-segment": (segments(512, [0, 512]), edges),
        }

    def build(seg_np):
        shape = (*seg_np.shape, H, d)
        return lambda: {
            "q": _normal(11, shape, dt),
            "k": _normal(12, shape, dt),
            "v": _normal(13, shape, dt),
            "w": _normal(23, shape),
            "seg": jnp.asarray(seg_np),
            "mask": jnp.asarray(_packed_mask(seg_np)),
        }

    def live(attn, inp):
        keep = (inp["seg"] != 0)[:, :, None, None]
        return lambda q, k, v: jnp.where(keep, attn(q, k, v), 0)

    for label, (seg_np, blocks) in grids.items():
        yield {
            "case": label,
            "build": build(seg_np),
            "kernel": lambda inp, blocks=blocks: _out_and_grads(
                live(
                    lambda q, k, v: attention.flash_train(
                        q, k, v, attention.flash_mask(inp["seg"], d, blocks), interpret=not compiled
                    ),
                    inp,
                ),
                inp["q"], inp["k"], inp["v"], inp["w"],
            ),
            "reference": lambda inp: _out_and_grads(
                live(lambda q, k, v: attention.sdpa_xla(q, k, v, inp["mask"], d), inp),
                inp["q"], inp["k"], inp["v"], inp["w"],
            ),
            # bf16 gradients sum rounded probabilities over up to 1024 keys
            "tol": 1e-1 if compiled else 2e-4,
        }


# ---------------------------------------------------------------------------
# block-sparse tree attention (ops/tree_attention.py)
# ---------------------------------------------------------------------------


@register_kernel("tree_attention")
def _cases_tree_attention(compiled: bool = False) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import tree_attention as ta

    N, H, d = (1024, 12, 128) if compiled else (ta.BLOCK, 2, 128)
    dt = jnp.bfloat16 if compiled else jnp.float32

    # a chain tree (parent = i-1) makes the ancestor mask exactly causal;
    # a branching tree exercises the sparse-block path
    chain = np.arange(-1, N - 1)
    branchy = np.where(np.arange(N) % 4 == 0, np.maximum(np.arange(N) - 4, -1),
                       np.arange(N) - 1).astype(np.int64)
    for label, parent in (("chain-causal", chain), ("branching", branchy)):
        words_np, block_any_np = ta.pack_ancestor_bits(parent)
        # dense reference mask, built from the parent pointers independently
        # of the packed words
        bits = np.zeros((N, N), bool)
        for i in range(N):
            if parent[i] >= 0:
                bits[i] = bits[parent[i]]
            bits[i, i] = True

        def build(words_np=words_np, block_any_np=block_any_np, bits=bits):
            return {
                "q": _normal(13, (N, H, d), dt),
                "k": _normal(14, (N, H, d), dt),
                "v": _normal(15, (N, H, d), dt),
                "w": _normal(29, (N, H, d)),  # cotangent for the backward
                "words": jnp.asarray(words_np),
                "block_any": jnp.asarray(block_any_np),
                "mask": jnp.asarray(bits)[None],  # [1, N, N] over heads
            }

        def ref(inp, q, k, v):
            logits = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * d**-0.5
            probs = jax.nn.softmax(jnp.where(inp["mask"], logits, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)

        def kern(inp, q, k, v):
            return ta.tree_attention(
                q, k, v, inp["words"], inp["block_any"], not compiled
            )

        def fwd(attn):
            return lambda inp: attn(inp, inp["q"], inp["k"], inp["v"])

        def grads(attn):
            def run(inp):
                g = jax.grad(
                    lambda q, k, v: (
                        attn(inp, q, k, v).astype(jnp.float32) * inp["w"]
                    ).sum(),
                    argnums=(0, 1, 2),
                )(inp["q"], inp["k"], inp["v"])
                return jnp.stack([x.astype(jnp.float32) for x in g])

            return run

        yield {
            "case": label,
            "build": build,
            "kernel": fwd(kern),
            "reference": fwd(ref),
            "tol": CHIP_TOL if compiled else 2e-4,
        }
        yield {
            "case": f"{label}-grads",
            "build": build,
            "kernel": grads(kern),
            "reference": grads(ref),
            "tol": 1e-1 if compiled else 2e-3,
        }


# ---------------------------------------------------------------------------
# megablox grouped matmul (the MoE expert FFN, models/moe.py)
# ---------------------------------------------------------------------------


@register_kernel("gmm")
def _cases_gmm(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.models.moe import pinned_gmm

    gmm = pinned_gmm()
    # rows x hidden x expert width, experts, m tile: moe.py takes the TPU
    # tile of 128 on the chip and 16 under the interpreter
    M, D, F, E, tm = (2048, 2048, 1024, 8, 128) if compiled else (64, 128, 128, 4, 16)
    dt = jnp.bfloat16 if compiled else jnp.float32
    rng = np.random.default_rng(41)
    cuts = np.sort(rng.integers(0, M + 1, E - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [M]])).astype(np.int32)
    group_of_row = np.repeat(np.arange(E), sizes)

    def reference(inp):
        # one plain matmul per group over its own rows (not ragged_dot: on
        # the TPU that is itself a grouped-matmul kernel)
        x, w = inp["x"].astype(jnp.float32), inp["w"].astype(jnp.float32)
        out = jnp.zeros((M, F), jnp.float32)
        for e in range(E):
            out = jnp.where((inp["group"] == e)[:, None], x @ w[e], out)
        return out

    yield {
        "case": "bf16-ragged-groups" if compiled else "f32-ragged-groups",
        "build": lambda: {
            "x": _normal(31, (M, D), dt),
            "w": _normal(37, (E, D, F), dt) * D**-0.5,
            "sizes": jnp.asarray(sizes),
            "group": jnp.asarray(group_of_row),
        },
        "kernel": lambda inp: gmm(
            inp["x"], inp["w"], inp["sizes"],
            tiling=(tm, 128, 128), interpret=not compiled,
        ),
        "reference": reference,
        "tol": CHIP_TOL if compiled else 2e-4,
    }


# ---------------------------------------------------------------------------
# the sampler's two launches over a step's logits (ops/vocab_block_stats.py):
# every block's maximum and sum of exponentials, then each row's chosen block,
# against the same partition in jnp over a padded copy of the row
# ---------------------------------------------------------------------------


@register_kernel("vocab_blocks")
def _cases_vocab_blocks(compiled: bool = False) -> Iterator[dict]:
    import jax.numpy as jnp

    from areal_tpu.ops import vocab_block_stats as vbs

    def case(label, slots, vocab, seed=3):
        width = vbs.block_width(vocab)
        nb = -(-vocab // width)

        def build():
            x = 3.0 * _normal(seed, (slots, vocab))
            x = x.at[0].set(-1e30).at[1, : vocab // 2].set(-jnp.inf)  # a masked row, blocks without a finite logit
            block = np.random.default_rng(seed).integers(0, nb, slots)
            block[:2] = nb - 1, 0
            return {"x": x, "block": jnp.asarray(block, jnp.int32)}

        def flat(inp, stats, rows):
            m, s = stats
            held = (inp["block"] * width)[:, None] + jnp.arange(width)[None] < vocab
            rows = jnp.where(held & jnp.isfinite(rows), rows, 0.0)
            return jnp.concatenate([jnp.where(jnp.isfinite(m), m, -1.0).reshape(-1), s.reshape(-1), rows.reshape(-1)])

        def kernel(inp):
            return flat(
                inp,
                vbs.vocab_block_stats(inp["x"], interpret=not compiled),
                vbs.vocab_block_pick(inp["x"], inp["block"], interpret=not compiled),
            )

        def reference(inp):
            return flat(inp, vbs.vocab_block_stats_xla(inp["x"]), vbs.vocab_block_pick_xla(inp["x"], inp["block"]))

        # sums of up to 4,096 exponentials in another order
        return {"case": label, "build": build, "kernel": kernel, "reference": reference, "tol": 1e-2 if compiled else 1e-3}

    if compiled:  # the cells' rows: Qwen2.5-1.5B, one chip's share of Kanana-2's, Phi-4-mini-flash
        yield case("128x151936", 128, 151936)
        yield case("64x19360", 64, 19360)
        yield case("64x200064", 64, 200064)
    else:
        yield case("16x19360", 16, 19360)
        yield case("8x4096-whole-blocks", 8, 4096)
    yield case("5x300-one-block", 5, 300)


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _cases_of(name: str, compiled: bool):
    # the on-chip flag is passed only when set, so a plain zero-argument
    # generator (tests register those) serves the default grid
    return REGISTRY[name](compiled=True) if compiled else REGISTRY[name]()


def _evaluate(spec: dict) -> "tuple[np.ndarray, np.ndarray]":
    """(kernel output, reference output) of one case, as f32 host arrays.
    Three programs a case — its inputs, the kernel, the reference — each
    compiled once for the one call it gets."""
    import jax

    if "build" not in spec:  # two plain zero-argument closures
        return (
            np.asarray(spec["kernel"](), np.float32),
            np.asarray(spec["reference"](), np.float32),
        )
    inputs = jax.jit(spec["build"])()
    got = np.asarray(jax.jit(spec["kernel"])(inputs), np.float32)
    # the TPU's default f32 matmul is a single bf16 pass: hold the
    # reference to full precision there
    with jax.default_matmul_precision("highest"):
        want = jax.jit(spec["reference"])(inputs)
    return got, np.asarray(want, np.float32)


def run_kernel(
    name: str, case: "int | str | None" = None, compiled: bool = False
) -> list[dict]:
    """Run one kernel's case grid; never raises on divergence — every
    case reports {kernel, index, case, max_abs_diff, tol, ok, error?,
    params?}. ``case`` filters to a single grid point by index or label
    (repro of one failing case without re-running the grid). ``compiled``
    runs the on-chip grid (interpret=False, Qwen2.5-1.5B head shapes) and
    needs a TPU."""
    results: list[dict] = []
    for idx, spec in enumerate(_cases_of(name, compiled)):
        if case is not None and case != idx and case != spec["case"]:
            continue
        rec: dict[str, Any] = {
            "kernel": name, "index": idx, "case": spec["case"],
            "tol": spec["tol"],
        }
        if "params" in spec:
            rec["params"] = spec["params"]
        try:
            got, want = _evaluate(spec)
            if got.shape != want.shape:
                rec.update(ok=False, error=f"shape {got.shape} vs {want.shape}")
            else:
                diff = float(np.max(np.abs(got - want)))
                rec.update(max_abs_diff=diff, ok=diff <= spec["tol"])
        except Exception as e:  # noqa: BLE001 — a crash IS a parity failure
            rec.update(ok=False, error=f"{type(e).__name__}: {e}")
        results.append(rec)
    return results


def run_all(
    only: str | None = None,
    case: "int | str | None" = None,
    compiled: bool = False,
) -> list[dict]:
    names = [only] if only else sorted(REGISTRY)
    out: list[dict] = []
    for name in names:
        out.extend(run_kernel(name, case=case, compiled=compiled))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="kernelcheck",
        description="interpret-vs-XLA parity for every registered ops/ kernel",
    )
    ap.add_argument("--list", action="store_true", help="enumerate kernels")
    ap.add_argument("--kernel", help="run one kernel's grid only")
    ap.add_argument(
        "--case",
        help="run a single grid point (index or label; requires --kernel) — "
        "re-run one failing case in isolation",
    )
    ap.add_argument("--json", action="store_true", help="JSON report")
    ap.add_argument(
        "--compiled",
        action="store_true",
        help="run the on-chip grid: interpret=False at Qwen2.5-1.5B head "
        "shapes (needs a TPU; chip_smoke.py's kernels phase)",
    )
    args = ap.parse_args(argv)
    if args.compiled:
        import jax

        if jax.default_backend() != "tpu":
            print("--compiled needs a TPU backend", file=sys.stderr)
            return 2

    if args.list:
        for name in sorted(REGISTRY):
            n = sum(1 for _ in _cases_of(name, args.compiled))
            print(f"{name}: {n} case(s)")
        return 0
    if args.kernel and args.kernel not in REGISTRY:
        print(f"unknown kernel {args.kernel!r}; known: {sorted(REGISTRY)}",
              file=sys.stderr)
        return 2
    case: int | str | None = None
    if args.case is not None:
        if not args.kernel:
            print("--case requires --kernel", file=sys.stderr)
            return 2
        case = int(args.case) if args.case.isdigit() else args.case
        known = list(_cases_of(args.kernel, args.compiled))
        if not any(
            case == i or case == c["case"] for i, c in enumerate(known)
        ):
            print(
                f"unknown case {args.case!r} for {args.kernel}; known: "
                f"{[c['case'] for c in known]}",
                file=sys.stderr,
            )
            return 2

    results = run_all(args.kernel, case=case, compiled=args.compiled)
    if args.json:
        print(json.dumps({"results": results}, indent=1))
    else:
        for r in results:
            if r["ok"]:
                print(
                    f"PASS {r['kernel']}:{r['case']} "
                    f"max_abs_diff={r.get('max_abs_diff', 0):.2e} tol={r['tol']:.0e}"
                )
            else:
                detail = r.get("error") or (
                    f"max_abs_diff={r['max_abs_diff']:.2e} > tol={r['tol']:.0e}"
                )
                print(f"FAIL {r['kernel']}:{r['case']} {detail}")
                # full repro line: the case-params dict plus the --case
                # incantation that re-runs just this grid point
                if "params" in r:
                    print(f"  params={r['params']}")
                print(
                    f"  repro: python -m areal_tpu.tools.kernelcheck "
                    f"--kernel {r['kernel']} --case {r['index']}"
                )
    failed = [r for r in results if not r["ok"]]
    if failed:
        print(f"kernelcheck: {len(failed)}/{len(results)} case(s) DIVERGED",
              file=sys.stderr)
        return 1
    # in --json mode stdout is the document; keep it parseable
    print(f"kernelcheck: {len(results)} case(s) ok",
          file=sys.stderr if args.json else sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
