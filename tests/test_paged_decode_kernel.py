"""``paged_decode_attn`` (ops/paged_attention_q8.py) in interpret mode against
``paged_kv.paged_attention_xla`` computed in float32 on the same inputs: the
serving dtypes (bf16 queries on bf16, int8 and fp8 pages, bf16 MXU operands
with f32 accumulation), the benchmark cells' head groupings (2 x 6, 4 x 7 and
olmo's 30 KV heads of one query row), a narrow and a wide page table, and
every length at which the block walk changes shape.

Every launch of this file and its two neighbours (test_paged_decode_launch.py,
test_paged_decode_shared.py) goes through ``LAUNCH``, one ``jax.jit`` of the
entry point: called eagerly a Pallas launch is traced, lowered and compiled
anew at EVERY call (3-7 s under the interpreter, 2 ms to run), jitted once a
shape, as the engine's programs hold it. Cases of one shape then share one
compile, so the work lists are padded with ended slots to one batch size.
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import paged_attention_stacked

# |kernel - float32 reference| allowed; the kernel's own error is the bf16
# rounding of its output (values under 1: 2e-3). Tighter than the 3e-2 of
# tests/test_paged_kernel_interpret.py, never to be loosened past it.
ATOL = 1e-2
PSZ, HD, L = 16, 128, 2
PAGES = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
LAUNCH = jax.jit(paged_attention_stacked, static_argnames=("pages_per_compute_block", "sm_scale", "interpret"))


def edge_lengths(wp: int, bk: int) -> np.ndarray:
    """Empty and live slots interleaved: 0, 1, one short of a page, a page,
    a block, one past a block, the full window."""
    full = wp * PSZ
    return np.asarray([0, 1, PSZ - 1, 0, PSZ, bk, min(bk + 1, full), 0, full], np.int32)


def build(G, KH, wp, pages, lengths, q_dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    S = len(lengths)
    N = S * wp + 1
    q = jnp.asarray(rng.normal(0, 1, (S, KH * G, HD)), q_dtype)
    k = jnp.asarray(rng.normal(0, 1, (L, KH, N, PSZ, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (L, KH, N, PSZ, HD)), jnp.float32)
    inp = dict(q=q, lengths=jnp.asarray(lengths), scales={})
    # page 0 is the pool's trash page; give it what a stale buffer must not leak
    k, v = k.at[:, :, 0].set(1e4), v.at[:, :, 0].set(1e4)
    if pages in (jnp.int8, jnp.float8_e4m3fn):
        k, ks = paged_kv.quantize_pages(k, dtype=pages)
        v, vs = paged_kv.quantize_pages(v, dtype=pages)
        inp["scales"] = dict(k_scales=ks, v_scales=vs)
    else:
        k, v = k.astype(pages), v.astype(pages)
    pt = 1 + rng.permutation(S * wp).reshape(S, wp)
    pt[np.asarray(lengths) == 0] = 0  # as the engine leaves an ended slot's row
    return dict(inp, k=k, v=v, pt=jnp.asarray(pt, jnp.int32))


def reference(inp, layer):
    """float32 gather + masked softmax on the same (de)quantized values."""
    f32 = lambda x: x.astype(jnp.float32)
    sc = inp["scales"]
    if sc:
        args = (inp["k"][layer], inp["v"][layer], inp["lengths"], inp["pt"],
                sc["k_scales"][layer], sc["v_scales"][layer])
    else:
        args = (f32(inp["k"][layer]), f32(inp["v"][layer]), inp["lengths"], inp["pt"])
    return np.asarray(paged_kv.paged_attention_xla(f32(inp["q"]), *args))


def check(inp, ppcb, layer=1, atol=ATOL):
    out = LAUNCH(
        inp["q"], inp["k"], inp["v"], jnp.int32(layer), inp["lengths"], inp["pt"],
        pages_per_compute_block=ppcb, interpret=True, **inp["scales"],
    )
    assert out.dtype == inp["q"].dtype
    out, live = np.asarray(out, np.float32), np.asarray(inp["lengths"]) > 0
    np.testing.assert_allclose(out[live], reference(inp, layer)[live], atol=atol)
    assert not out[~live].any(), "a zero-length slot returns exact zeros"


@pytest.mark.parametrize("G,KH", [(6, 2), (7, 4), (1, 30)])
@pytest.mark.parametrize("pages", sorted(PAGES))
def test_bf16_queries_match_the_float32_reference(pages, G, KH):
    wp = 4
    ppcb = paged_kv.choose_ppcb(wp)  # the decode step's own choice: 4
    check(build(G, KH, wp, PAGES[pages], edge_lengths(wp, ppcb * PSZ)), ppcb)


# work lists by what the item loop meets in them, as (table width in pages,
# tokens a block) -> lengths; the table holds at least two blocks
WALKS = {
    "edges": edge_lengths,
    "odd_count": lambda wp, bk: [bk + 1, 0, 1],  # 3 items
    "even_count": lambda wp, bk: [bk + 1, 0, 1, bk],  # 4 items
    # consecutive items (0, 1) and (2, 3) each lie in two slots
    "straddle": lambda wp, bk: [1, 2 * bk, 5],
    "one_block": lambda wp, bk: [bk],
    "one_token": lambda wp, bk: [1],
    "full_table": lambda wp, bk: [wp * PSZ, wp * PSZ],
    "dead_between": lambda wp, bk: [bk + 3, 0, 7],
}


def walk_lengths(walk: str, wp: int, bk: int) -> np.ndarray:
    """The walk's lengths, then ended slots up to the nine of ``edges``: an
    ended slot adds no item to the list, and every walk of one block size is
    one traced launch."""
    lengths = np.asarray(WALKS[walk](wp, bk), np.int32)
    return np.pad(lengths, (0, 9 - len(lengths)))


@pytest.mark.parametrize("pages,G,KH", [("bf16", 6, 2), ("int8", 7, 4)])
def test_wide_table(pages, G, KH):
    """A 32-page table, as both cells run: most of a slot's row is never
    read, and the full window walks 8 blocks."""
    wp = 32
    ppcb = paged_kv.choose_ppcb(wp)
    check(build(G, KH, wp, PAGES[pages], edge_lengths(wp, ppcb * PSZ), seed=1), ppcb)
