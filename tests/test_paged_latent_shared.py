"""``paged_latent_attn`` over a page table that aliases pages (a GRPO group's
siblings hold the first one's prompt pages): the launch walks
``shared_decode_schedule()``'s list, a shared block of latent rows is fetched
once and its readers' query rows stacked on one matmul a pass, and the
outputs are those of the list that fetches every slot's blocks for that slot
alone (tests/test_paged_decode_shared.py is the K/V twin, and tests the list
itself).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import DecodeItems, decode_schedule, shared_decode_schedule
from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked, pass_readers

L, PSZ, LANES, VALUE, WP, PPCB = 2, 8, 256, 128, 12, 2
BK = PPCB * PSZ
SCALE = LANES**-0.5
# samples of one prompt of 3 blocks and a part (6 full pages: blocks 0-2 are what siblings can share), by where each
# stands against the last shared block: past it and in its own first block, a token past it, AT its end (block 2 is
# then the slot's last, so its own), INSIDE it, and further along by other amounts, one at the table's end
PROMPT = 3 * BK + 5
# one ``jax.jit`` of the entry point, and every group padded with ended slots to one batch: see tests/test_paged_decode_kernel.py
LAUNCH = jax.jit(paged_latent_attention_stacked, static_argnames=("value_lanes", "pages_per_compute_block", "sm_scale", "interpret"))
MEMBERS = [PROMPT + 1, 3 * BK + 1, 3 * BK, 2 * BK + 8, 4 * BK, 4 * BK + 1, 5 * BK, WP * PSZ, PROMPT + 4, 4 * BK + 8, 5 * BK + 1]


def aliased(groups, heads, pages, seed=0):
    """Inputs whose table the pool could have made. ``groups``: a list of
    (prompt tokens, [cached tokens of each member]); every member after the
    first holds the first's ``prompt // PSZ`` full prompt pages and pages of
    its own from there. A member of length 0 has ended: its row points at
    page 0, whose rows would swamp any sum they entered."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray([n for _, members in groups for n in members], np.int32)
    S = len(lengths)
    pt = 1 + rng.permutation(S * WP).reshape(S, WP)
    b = 0
    for prompt, members in groups:
        live = [b + j for j, n in enumerate(members) if n]
        for s in live[1:]:
            pt[s, : prompt // PSZ] = pt[live[0], : prompt // PSZ]
        b += len(members)
    pt[lengths == 0] = 0
    pool = jnp.asarray(rng.normal(0, 1, (L, 1, S * WP + 1, PSZ, LANES)), jnp.float32).at[:, :, 0].set(1e4)
    return dict(
        q=jnp.asarray(rng.normal(0, 1, (S, heads, LANES)), pages), pool=pool.astype(pages),
        lengths=jnp.asarray(lengths), pt=jnp.asarray(pt, jnp.int32),
    )


def launch(inp, schedule=None, select=None, layer=1):
    return np.asarray(
        LAUNCH(
            inp["q"], inp["pool"], jnp.int32(layer), inp["lengths"], inp["pt"], value_lanes=VALUE,
            pages_per_compute_block=PPCB, sm_scale=SCALE, schedule=schedule, select=select, interpret=True,
        )
    )


def gathered(inp, select=None, layer=1):
    pool = inp["pool"][layer].astype(jnp.float32)
    out = paged_kv.paged_attention_xla(inp["q"].astype(jnp.float32), pool, pool, inp["lengths"], inp["pt"], sm_scale=SCALE, select=select)
    return np.asarray(out[..., :VALUE])


def private(inp):
    return DecodeItems.private(decode_schedule(inp["lengths"], WP, PSZ, PPCB))


@pytest.mark.parametrize("pages", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("readers", [1, 2, 4, 8, 11])
def test_shared_latent_blocks_give_the_outputs_of_blocks_fetched_a_slot(readers, heads, pages):
    """A group of ``readers`` samples beside a slot of its own and an ended
    one: against the gather path at tests/test_kanana2_kernels.py's tolerance
    (kernelcheck's: bfloat16 probabilities meet the values rounded), and
    against the launch over one item a (slot, block). A slot meets its blocks
    in the same order under both lists and a row of the stacked matmul holds
    the products the single reader's row holds; the CPU's matmul sums them in
    another order when more rows are stacked, so float32 pages agree to 1e-6
    and bfloat16 ones to a step of their type."""
    group = MEMBERS[:readers] + [0] * (len(MEMBERS) - readers)  # the samples that have ended: every case is one batch of 13
    inp = aliased([(0, [0]), (PROMPT, group), (0, [2 * BK + 3])], heads, pages, seed=readers)
    items, fetch = shared_decode_schedule(inp["lengths"], inp["pt"], PSZ, PPCB)
    n_shared, n_items = (int(c) for c in items.count)
    # blocks 0-2 are shared by the members past them; 11 readers of block 0 are two items
    past = [sum(n > (i + 1) * BK for n in MEMBERS[:readers]) for i in range(3)]
    assert n_shared == sum(-(-k // 8) for k in past if k > 1)
    assert n_items == int(fetch.blocks) <= int(fetch.blocks_listed) and (n_shared > 0) == (n_items < int(fetch.blocks_listed))
    out = launch(inp, items)
    live = np.asarray(inp["lengths"]) > 0
    np.testing.assert_allclose(out[live], gathered(inp)[live], atol=1e-5 if pages == jnp.float32 else 3e-2, rtol=0)
    assert not out[~live].any()
    tol = dict(atol=1e-6, rtol=0) if pages == jnp.float32 else dict(atol=1e-3, rtol=2.0**-7)
    np.testing.assert_allclose(out, launch(inp, private(inp)), **tol)


@pytest.mark.parametrize("pages", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_a_table_without_aliases_walks_the_slot_major_list(pages):
    """No shared item: ``decode_schedule()``'s items in its order, and the
    outputs of the launch handed that list; the list made inside the call is
    the same one."""
    inp = aliased([(0, [n]) for n in (1, BK, 0, BK + 1, WP * PSZ, 3) + (0,) * 7], 32, pages)  # a batch of 13, as above
    items, fetch = shared_decode_schedule(inp["lengths"], inp["pt"], PSZ, PPCB)
    slot, block, n = decode_schedule(inp["lengths"], WP, PSZ, PPCB)
    n = int(n[0])
    assert [int(c) for c in items.count] == [0, n] and int(fetch.blocks_listed) == n
    np.testing.assert_array_equal(np.asarray(items.slot)[:n], np.asarray(slot)[:n])
    np.testing.assert_array_equal(np.asarray(items.block)[:n], np.asarray(block)[:n])
    assert int(fetch.tokens) == int(np.asarray(inp["lengths"]).sum())
    out = launch(inp, items)
    np.testing.assert_array_equal(out, launch(inp, (slot, block, jnp.asarray([n], jnp.int32))))
    np.testing.assert_array_equal(out, launch(inp))


@pytest.mark.parametrize("heads", [32, 64])
def test_a_selection_reads_over_the_private_list_of_an_aliased_table(heads):
    """With ``select`` the launch walks one item a (slot, block) whatever the
    table aliases (a shared block would need every reader's mask rows): the
    gather path's outputs under the same mask, whole blocks of a slot
    without a chosen token among them, and the list made inside the call is
    that one."""
    inp = aliased([(PROMPT, MEMBERS[:4]), (0, [0, BK + 3])], heads, jnp.float32, seed=3)
    lengths = np.asarray(inp["lengths"])
    rng = np.random.default_rng(1)
    cached = np.arange(WP * PSZ)[None, :] < lengths[:, None]
    chosen = cached & (rng.random(cached.shape) < 0.2)
    chosen[:, BK : 2 * BK] = False  # a whole block of every slot unchosen
    chosen[np.arange(len(lengths)), np.maximum(lengths - 1, 0)] = lengths > 0  # every slot with tokens selects one
    chosen = jnp.asarray(chosen)
    out = launch(inp, private(inp), select=chosen)
    live = lengths > 0
    np.testing.assert_allclose(out[live], gathered(inp, select=chosen)[live], atol=1e-5, rtol=0)
    assert not out[~live].any()
    np.testing.assert_array_equal(out, launch(inp, select=chosen))


@pytest.mark.parametrize("heads,want", [(4, (8, 8)), (8, (8, 8)), (16, (4, 8)), (32, (2, 4)), (40, (1, 2)), (64, (1, 2)), (128, (1, 2))])
def test_passes_take_the_readers_that_fill_64_and_128_rows(heads, want):
    assert pass_readers(heads) == want and all(8 % n == 0 for n in want)


def test_a_decode_step_counts_what_the_latent_launch_fetches(monkeypatch):
    """One decode step of the tiny latent model over a table in which three
    samples hold one prompt's pages, on the kernel path (every launch
    interpreted) and on the gather path: the same hidden rows;
    ``latent_tokens_read`` a layer is the DISTINCT cached tokens where the
    shared list is walked and every live slot's cached tokens on the gather
    path; the launch lists fewer blocks than the live slots' rows hold."""
    import functools
    import os
    import sys

    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
    import chipbench_kanana2_util as ku

    import areal_tpu.ops.paged_kv_write as pkw
    import areal_tpu.ops.paged_latent_attention as pla
    from areal_tpu.models import hybrid

    monkeypatch.setattr(pla, "paged_latent_attention_stacked", functools.partial(pla.paged_latent_attention_stacked, interpret=True))
    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    cfg = ku.tiny_model(held=8)
    mcfg, params = ku.model_config(cfg), ku.make_params(cfg, 3)
    assert set(mcfg.count_shapes) >= {"latent_tokens_read", "attn_blocks_listed", "attn_blocks_fetched"}
    S, psz, wp = 5, 8, 8  # blocks of 4 pages: the prompt's first 4 pages are one shared block
    positions = np.array([40, 35, 0, 47, 9])  # this step's token goes to a page of the slot's own
    active = np.array([True, True, False, True, True])
    pt = np.zeros((S, wp), np.int32)
    for b in np.flatnonzero(active):
        pt[b] = 1 + b * wp + np.arange(wp)
    pt[1, :4] = pt[3, :4] = pt[0, :4]
    rng = np.random.default_rng(0)
    out = {}
    for use_kernel in (False, True):
        cache = paged_kv.init_paged_cache(mcfg, S * wp + 1, psz, slots=S)
        cache = {**cache, "k": jnp.asarray(rng.normal(0, 1, cache["k"].shape), cache["k"].dtype)}
        cache = {**cache, **{k: jnp.zeros(s, jnp.int32) for k, s in mcfg.count_shapes.items()}}
        rng = np.random.default_rng(0)  # the same pool on both paths
        h, cache = hybrid.forward_decode_paged(
            params, mcfg, jnp.array([7, 9, 0, 11, 5]), jnp.asarray(positions), cache, jnp.asarray(pt), page_size=psz,
            active=jnp.asarray(active), use_kernel=use_kernel,
        )
        out[use_kernel] = (np.asarray(h)[active], {k: np.asarray(cache[k]) for k in mcfg.count_shapes})
    jax.effects_barrier()
    (h_xla, c_xla), (h_krn, c_krn) = out[False], out[True]
    np.testing.assert_allclose(h_krn, h_xla, atol=2e-5, rtol=0)  # test_kanana2_model.py's tolerance
    cached = int((positions + 1)[active].sum())
    layers = mcfg.count("mla")
    assert c_xla["latent_tokens_read"].tolist() == [cached] * layers  # as tests/test_kanana2_engine.py pins it
    assert c_krn["latent_tokens_read"].tolist() == [cached - 2 * 32] * layers  # two siblings' copies of the shared block
    assert (c_krn["attn_blocks_listed"].tolist(), c_krn["attn_blocks_fetched"].tolist()) == ([7], [5])
    assert (c_xla["attn_blocks_listed"].tolist(), c_xla["attn_blocks_fetched"].tolist()) == ([0], [0])  # no work list
