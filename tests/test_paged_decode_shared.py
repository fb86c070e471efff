"""``paged_decode_attn`` over a page table that aliases pages (a GRPO group's
siblings hold the first one's prompt pages): the work list names each
distinct block once with the slots that read it
(``shared_decode_schedule``), and the launch's outputs are those of the
list that fetches every slot's blocks for that slot alone.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import (
    MAX_READERS,
    DecodeItems,
    decode_schedule,
    shared_decode_schedule,
)
from tests.test_paged_decode_kernel import HD, L, LAUNCH, PAGES, PSZ, reference

SLOTS = 11  # of the widest table (``eleven_readers``): what ``aliased(..., slots=SLOTS)`` pads every table's batch to


def aliased(groups, wp, G, KH, pages=jnp.bfloat16, q_dtype=jnp.bfloat16, seed=0, slots=0):
    """Inputs whose table the pool could have made. ``groups``: a list of
    (prompt tokens, [cached tokens of each member]); every member after the
    first holds the first's ``prompt // PSZ`` full prompt pages and pages of
    its own from there (its boundary page is a private copy). A member of
    length 0 has ended: its row points at page 0. ``slots``: ended slots are
    appended up to that batch size (they add no item: the launches of one
    block size and head grouping are then one traced program)."""
    rng = np.random.default_rng(seed)
    groups = list(groups) + [(0, [0])] * (slots - sum(len(members) for _, members in groups))
    lengths = np.asarray([n for _, members in groups for n in members], np.int32)
    S = len(lengths)
    N = S * wp + 1
    pt = 1 + rng.permutation(S * wp).reshape(S, wp)
    b = 0
    for prompt, members in groups:
        live = [b + j for j, n in enumerate(members) if n]
        for s in live[1:]:
            pt[s, : prompt // PSZ] = pt[live[0], : prompt // PSZ]
        b += len(members)
    pt[lengths == 0] = 0
    q = jnp.asarray(rng.normal(0, 1, (S, KH * G, HD)), q_dtype)
    k = jnp.asarray(rng.normal(0, 1, (L, KH, N, PSZ, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (L, KH, N, PSZ, HD)), jnp.float32)
    k, v = k.at[:, :, 0].set(1e4), v.at[:, :, 0].set(1e4)
    inp = dict(q=q, lengths=jnp.asarray(lengths), scales={})
    if pages in (jnp.int8, jnp.float8_e4m3fn):
        k, ks = paged_kv.quantize_pages(k, dtype=pages)
        v, vs = paged_kv.quantize_pages(v, dtype=pages)
        inp["scales"] = dict(k_scales=ks, v_scales=vs)
    else:
        k, v = k.astype(pages), v.astype(pages)
    return dict(inp, k=k, v=v, pt=jnp.asarray(pt, jnp.int32))


def walk(items: DecodeItems, nb: int):
    """[(block, [readers])] of the list's items, shared ones first."""
    slot, block, count, nxt = (np.asarray(a) for a in items)
    out = []
    for t in range(int(count[1])):
        readers = [int(slot[t])]
        if t < count[0]:
            while len(readers) < MAX_READERS and nxt[readers[-1] * nb + block[t]] >= 0:
                readers.append(int(nxt[readers[-1] * nb + block[t]]))
        out.append((int(block[t]), readers))
    return out, int(count[0])


# tables by what the list meets in them: (table width in pages, groups)
BK2 = 2 * PSZ  # at ppcb 2
TABLES = {
    # 8 samples of one prompt of 5 pages and a part: 2 shared blocks at ppcb 2 (the fifth page's block is private: it
    # holds the boundary page), every sibling further along by a different amount
    "group_of_8": (8, [(5 * PSZ + 3, [5 * PSZ + 4 + 5 * j for j in range(8)])]),
    # the shared prefix ends inside a block: 3 pages at ppcb 2 or 4 share 1 block or none
    "prefix_ends_inside_a_block": (8, [(3 * PSZ, [3 * PSZ + 1, 3 * PSZ + 20, 4 * PSZ])]),
    # one sibling has ended (row at page 0), one has outlived the rest
    "ended_and_survivor": (8, [(4 * PSZ + 1, [0, 0, 7 * PSZ + 5, 0]), (4 * PSZ, [4 * PSZ + 2, 0, 4 * PSZ + 9])]),
    # two groups and ungrouped slots mixed, their slots interleaved with dead ones
    "mixed": (8, [(4 * PSZ, [4 * PSZ + 1, 5 * PSZ]), (0, [3 * PSZ + 1]), (0, [0]), (6 * PSZ + 8, [6 * PSZ + 9, 7 * PSZ, 8 * PSZ]), (0, [1])]),
    # more readers than one item takes: 11 samples of a prompt of 4 pages
    "eleven_readers": (8, [(4 * PSZ, [4 * PSZ + 1 + j for j in range(11)])]),
    # nothing aliased
    "ungrouped": (8, [(0, [n]) for n in (1, BK2, 0, BK2 + 1, 8 * PSZ, 3)]),
}


@pytest.mark.parametrize("ppcb", [1, 2, 4])
@pytest.mark.parametrize("table", TABLES)
def test_every_block_of_a_live_slot_is_read_once(table, ppcb):
    """Every (live slot, block with tokens) is in exactly one item, as first
    reader or further along its chain; readers of one item name the same
    pages and are all past the block; a slot meets its blocks in ascending
    order; the fetched tokens are those of the items' blocks, which are the
    distinct cached tokens wherever no page lies in two items (a prefix that
    ends inside a block leaves its last pages in every sibling's own block;
    a group past an item's readers is fetched once an item)."""
    wp, groups = TABLES[table]
    inp = aliased(groups, wp, 1, 1, slots=SLOTS)
    lengths, pt = np.asarray(inp["lengths"]), np.asarray(inp["pt"])
    nb, bk = wp // ppcb, ppcb * PSZ
    items, fetch = shared_decode_schedule(inp["lengths"], inp["pt"], PSZ, ppcb)
    got, n_shared = walk(items, nb)
    seen, distinct, fetched = {}, set(), 0
    for t, (i, readers) in enumerate(got):
        rows = {tuple(pt[b, i * ppcb : (i + 1) * ppcb]) for b in readers}
        assert len(rows) == 1, "the readers of an item name the same pages"
        assert (len(readers) > 1) <= (t < n_shared)
        for b in readers:
            assert (b, i) not in seen
            assert lengths[b] > (i + 1) * bk or len(readers) == 1, "a shared block is full, and not its reader's last"
            assert all(j < i for (b2, j) in seen if b2 == b), "ascending blocks a slot"
            seen[b, i] = t
        first = readers[0]
        tokens = {(int(pt[first, i * ppcb + p // PSZ]), p % PSZ) for p in range(min(bk, lengths[first] - i * bk))}
        fetched += len(tokens)
        distinct |= tokens
    want = {(b, i) for b in range(len(lengths)) for i in range(-(-int(lengths[b]) // bk))}
    assert set(seen) == want
    assert int(fetch.tokens) == fetched and int(fetch.blocks) == len(got)
    if table in ("group_of_8", "ended_and_survivor", "ungrouped") and ppcb == 1:
        assert fetched == len(distinct) and (table == "ungrouped" or fetched < lengths.sum())
    assert int(fetch.blocks_listed) == len(want)
    # and as few items as the table allows, but for groups past an item's readers
    blocks = {(i, tuple(pt[b, i * ppcb : (i + 1) * ppcb])) for b, i in want}
    if table != "eleven_readers":
        assert len(got) == len(blocks)


@pytest.mark.parametrize("ppcb", [1, 2, 4])
def test_a_table_without_aliases_gives_the_slot_major_list(ppcb):
    wp, groups = TABLES["ungrouped"]
    inp = aliased(groups, wp, 1, 1)
    items, fetch = shared_decode_schedule(inp["lengths"], inp["pt"], PSZ, ppcb)
    slot, block, n = decode_schedule(inp["lengths"], wp, PSZ, ppcb)
    n = int(n[0])
    assert [int(c) for c in items.count] == [0, n] and int(fetch.blocks_listed) == n
    np.testing.assert_array_equal(np.asarray(items.slot)[:n], np.asarray(slot)[:n])
    np.testing.assert_array_equal(np.asarray(items.block)[:n], np.asarray(block)[:n])
    assert int(fetch.tokens) == int(np.asarray(inp["lengths"]).sum())


def launch(inp, ppcb, schedule=None, layer=1):
    return np.asarray(
        LAUNCH(
            inp["q"], inp["k"], inp["v"], jnp.int32(layer), inp["lengths"], inp["pt"],
            pages_per_compute_block=ppcb, schedule=schedule, interpret=True, **inp["scales"],
        ),
        np.float32,
    )


def check_shared(inp, wp, ppcb, atol=1e-2, rtol=2.0**-7):
    """The launch over the shared list against the float32 reference, and
    against the launch that fetches every block a slot (both lists handed over
    as ``DecodeItems``: one traced program). A slot meets its blocks in the
    same order under both lists, and a row of the stacked matmul holds the
    products the single reader's row holds; the CPU's matmul sums them in
    another order when more rows are stacked, so the outputs agree to float32
    rounding and not bit for bit: here to one step of the output's type
    (``rtol``: bfloat16's 2^-7). Returns the shared list's outputs."""
    out = launch(inp, ppcb, shared_decode_schedule(inp["lengths"], inp["pt"], PSZ, ppcb)[0])
    live = np.asarray(inp["lengths"]) > 0
    np.testing.assert_allclose(out[live], reference(inp, 1)[live], atol=atol)
    assert not out[~live].any()
    alone = launch(inp, ppcb, DecodeItems.private(decode_schedule(inp["lengths"], wp, PSZ, ppcb)))
    np.testing.assert_allclose(out, alone, rtol=rtol, atol=1e-6)
    return out


@pytest.mark.parametrize("table,ppcb", [("group_of_8", 2), ("group_of_8", 1), ("prefix_ends_inside_a_block", 2), ("ended_and_survivor", 4), ("mixed", 2), ("eleven_readers", 4)])
def test_shared_blocks_give_the_outputs_of_blocks_fetched_a_slot(table, ppcb):
    wp, groups = TABLES[table]
    check_shared(aliased(groups, wp, 4, 2, seed=ppcb, slots=SLOTS), wp, ppcb)


@pytest.mark.parametrize("G,KH,pages", [(1, 30, "bf16"), (7, 4, "bf16"), (6, 2, "int8"), (2, 2, "fp8")])
def test_shared_blocks_by_head_grouping_and_page_type(G, KH, pages):
    """olmo's 30 KV heads of one query row (a reader takes one row of the
    stacked matmul), 7 rows a head (a reader's rows padded to a tile), and
    quantized pages (the scale pools ride in the same copies)."""
    wp, groups = TABLES["mixed"]
    check_shared(aliased(groups, wp, G, KH, pages=PAGES[pages], seed=G), wp, 2)


def test_float32_queries_over_shared_blocks():
    wp, groups = TABLES["group_of_8"]
    inp = aliased(groups, wp, 4, 2, pages=jnp.float32, q_dtype=jnp.float32)
    out = check_shared(inp, wp, 2, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(out, launch(inp, 2))  # the list made inside the call is the shared one
    slot_major = decode_schedule(inp["lengths"], wp, PSZ, 2)  # handed over as it is: made ``DecodeItems`` inside the call
    np.testing.assert_array_equal(launch(inp, 2, slot_major), launch(inp, 2, DecodeItems.private(slot_major)))
