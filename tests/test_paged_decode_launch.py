"""The launch of ``paged_decode_attn``: its work list, its block sizes, its
float32 path, and the decode step's call site that keeps ended slots out.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import decode_schedule
from tests.test_paged_decode_kernel import LAUNCH, PAGES, PSZ, WALKS, build, check, edge_lengths, walk_lengths


def test_all_empty_launch_returns_zeros():
    inp = build(6, 2, 4, jnp.bfloat16, np.zeros(5, np.int32))
    out = LAUNCH(
        inp["q"], inp["k"], inp["v"], jnp.int32(0), inp["lengths"], inp["pt"],
        pages_per_compute_block=2, interpret=True,
    )
    assert out.shape == inp["q"].shape and not np.asarray(out, np.float32).any()


def test_float32_queries_keep_the_float32_path():
    """f32 operands stay f32 (tests, tools): the error is f32 rounding, far
    inside the old 3e-2."""
    wp = 4
    inp = build(6, 2, wp, jnp.float32, edge_lengths(wp, 2 * PSZ), q_dtype=jnp.float32)
    check(inp, 2, atol=1e-5)


@pytest.mark.parametrize("ppcb", [1, 2, 4])
@pytest.mark.parametrize("walk", WALKS)
def test_block_sizes_agree(walk, ppcb):
    """1, 2 and 4 pages a block over the same lengths, the last block of a
    slot partly fetched: what the unfetched pages' buffers hold (here the
    trash page's 1e4) never reaches the output. By work list: an odd and an
    even item count, consecutive items in two slots, a slot of one block, of
    one token, of the whole table, a dead slot between two live ones."""
    wp = 8
    check(
        build(7, 4, wp, jnp.bfloat16, walk_lengths(walk, wp, ppcb * PSZ), seed=ppcb), ppcb
    )


@pytest.mark.parametrize("pages", ["int8", "fp8"])
@pytest.mark.parametrize("walk", ["odd_count", "straddle"])
def test_quantized_pages_walk_the_same_lists(walk, pages):
    """The scale pools ride in the same copies: the lists that change the
    loop's shape, over int8 and fp8 pages at 2 x 6."""
    wp, ppcb = 8, 2
    check(build(6, 2, wp, PAGES[pages], walk_lengths(walk, wp, ppcb * PSZ), seed=5), ppcb)


def test_schedule_lists_the_live_blocks_in_slot_order():
    lengths = np.asarray([0, 1, 40, 0, 32, 64, 0], np.int32)
    slot, block, n = decode_schedule(jnp.asarray(lengths), 4, 16, 2)  # 32-token blocks
    want = [(1, 0), (2, 0), (2, 1), (4, 0), (5, 0), (5, 1)]
    assert int(n[0]) == len(want) and slot.shape == block.shape == (7 * 2,)
    got = list(zip(np.asarray(slot)[: len(want)], np.asarray(block)[: len(want)]))
    assert got == want
    assert int(decode_schedule(jnp.zeros(7, jnp.int32), 4, 16, 2)[2][0]) == 0


def test_a_given_schedule_must_fit_and_changes_nothing():
    wp = 4
    inp = build(6, 2, wp, jnp.int8, edge_lengths(wp, 2 * PSZ), seed=3)
    call = functools.partial(
        LAUNCH, inp["q"], inp["k"], inp["v"], jnp.int32(0),
        inp["lengths"], inp["pt"], pages_per_compute_block=2, interpret=True,
        **inp["scales"],
    )
    own = call()
    given = call(schedule=decode_schedule(inp["lengths"], wp, PSZ, 2))
    np.testing.assert_array_equal(np.asarray(own, np.float32), np.asarray(given, np.float32))
    with pytest.raises(ValueError, match="schedule"):
        call(schedule=decode_schedule(inp["lengths"], wp, PSZ, 1))


def test_decode_step_leaves_ended_slots_out(monkeypatch):
    """An ended slot keeps its last position and has its table row pointed at
    the trash page: the decode step hands the kernel length 0 for it (no
    item in the work list), and live slots read as on the gather path."""
    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw
    from areal_tpu.models import qwen

    seen = {}

    def spy(q, k, v, li, lengths, pt, *, schedule, **kw):
        seen["lengths"], seen["schedule"] = lengths, schedule
        return real(q, k, v, li, lengths, pt, schedule=schedule, interpret=True, **kw)

    real = q8mod.paged_attention_stacked
    monkeypatch.setattr(q8mod, "paged_attention_stacked", spy)
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))
    cfg = qwen.ModelConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=8, num_kv_heads=2, head_dim=16, dtype="float32",
        tie_word_embeddings=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    S, psz, wp = 4, 16, 2
    cache = paged_kv.init_paged_cache(cfg, S * wp + 1, psz)
    pt = 1 + np.arange(S * wp).reshape(S, wp)
    pt[[1, 3]] = 0  # ended: rows at the trash page, positions stale
    ids = jnp.asarray([3, 5, 7, 9], jnp.int32)
    pos = jnp.asarray([4, 30, 14, 19], jnp.int32)
    hid = {
        uk: np.asarray(qwen.forward_decode_paged(
            params, cfg, ids, pos, dict(cache), jnp.asarray(pt, jnp.int32),
            page_size=psz, use_kernel=uk,
        )[0])
        for uk in (True, False)
    }
    np.testing.assert_array_equal(np.asarray(seen["lengths"]), [5, 0, 15, 0])
    assert [int(n) for n in seen["schedule"].count] == [0, 2]  # no shared item, two in all
    np.testing.assert_allclose(hid[True][[0, 2]], hid[False][[0, 2]], atol=1e-4)
