"""Time the decode step's two paged kernels alone on the chip at the rollout
cells' shapes: ``paged_decode_attn`` and ``paged_kv_write``.

    chiprun -- python -m areal_tpu.tools.decode_attn_probe

``paged_decode_attn``: one launch per layer over a stacked bf16 pool, as the decode step
makes them (128-token pages, a 32-page table), for three sets of lengths:

  ``mix``     36% of the slots live, lengths drawn like the benchmark's
              ``grpo-reasoning`` traffic (prompt log-uniform 128-1024 plus a
              uniform share of a lognormal output, median 384)
  ``empty``   no slot live: the launch's fixed cost
  ``mix512``  the same live set, every length rounded up to 512: what whole
              512-token blocks cost against ``mix``

and prints microseconds a launch, the KV bytes the lengths need and their
share of the chip's memory roofline, and beside each ``mix`` / ``mix512`` row
the items a launch walks ((live slot, 512-token block) pairs) and the
microseconds an item the launch takes over its bytes' time. The shapes are
the two Qwen cells' and the olmo cell's (64 slots, 30 KV heads of one query
row: the widest item the kernel serves).

``--group 1,2,4,8`` (alone): ``paged_decode_attn`` over a table in which the
live slots are groups of that many samples of one prompt, each sibling
holding the first one's full prompt pages (``SlotCache.alias``), at the three
shapes above under ``grpo-reasoning``'s lengths and at the long-context
cell's (64 slots, 10 K/V rows of 128 under 4 float32 query rows, a 160-page
table, 24 live slots over prompts of 4k-16k tokens): us a launch over the
list that names each distinct block once (``shared_decode_schedule``) and
over the list that fetches every block a slot (``decode_schedule``), blocks
fetched of blocks listed, the bytes each fetches and the launch's share of
the roofline of the bytes IT fetches, and the list's own microseconds a step.

``paged_latent_attn`` (``--only latent`` runs it alone): the latent-attention
cell's launch, 32 query rows of 640 lanes over ONE stacked pool of 1,280 B
rows (576 published values in 640 lanes), 48 layers, by live slots (16 / 24 /
48 of 64) and by cached tokens (the ``mix`` lengths, and every live slot at
1k / 4k; at 24 live slots 300 and 600 too: partly filled last blocks), against the bytes and operations of the PUBLISHED row (1,152 B and
69,632 operations a cached token and layer), and the row write of the same
pool (one ``paged_kv_write`` launch a layer over the live slots). With
``--group 1,2,4,8`` (``--heads 32,64``): the launch alone over the
long-context cells' table (160 pages a row, 24 of 64 slots live in groups of
that many samples of one prompt of 4k-16k tokens, as the K/V ``--group`` rows):
us a launch over ``shared_decode_schedule()``'s list (a shared block fetched
once, its readers' query rows stacked a pass) and over one item a (slot,
block), each with its share of the time of the published rows' bytes it
fetches, blocks fetched of listed, and ``max_abs_diff`` between the two
outputs; ``--group 1`` and the ``mix`` rows above say what a table without
aliases costs.

``--only dsa``: the sparse read of a latent-attention layer with a learned
index, at the long-context cell's shapes (64 slots, 64 query heads, 6 layers,
a 160-page table, 2,048 of up to 20k cached tokens selected): the index's
launch (``paged_index_scores``) against its keys' bytes, the exact top-2,048
two ways (``hybrid.select_top``'s 32 counting passes, and ``jax.lax.top_k``),
and the read two ways: MASKED (``paged_latent_attn`` over every page that
holds tokens, the unselected masked) and GATHERED (the selected rows fetched
by index into [slots, 2048, 640] and the absorbed products over them, in XLA:
the form the program does not keep), by cached tokens a slot.

``--only latent-prefill``: the PROMPT pass's attention of a latent-attention
layer alone (``hybrid.prefill_attend_block`` over every query block of one
prompt, the keys and values already made): the XLA loop in its own blocks
against the one launch a query block (``mla_prefill_flash``), at 1,024 /
4,096 / 8,192 / 16,384 tokens, 32 heads (128 + 64 | 128: the kanana cell's)
and 64 (192 + 64 | 256: the GLM-5 cell's), causal and with a selection of
2,048 keys a query as a mask operand, by (queries, keys) a block: us a layer
and TFLOP/s over the operations under the causal mask, L (L + 1) / 2 x
heads x 2 x (nope + rope + v). The table that sets ``hybrid.prefill_blocks``
and ``prefill_takes_launch``.

``paged_kv_write``: a step's KV rows of every layer written into the stacked
pools (``paged_kv.write_decode_rows``) by the per-head XLA scatters over every
slot and by the one ``paged_kv_write`` launch over the live slots, at 0 / 25 /
50 / 100% of the slots live, bf16 and int8 pages: microseconds a layer, and
whether both left the same bits in every page but the trash page (the pools
are random, so a tile of another layer, head, page or slot put back shows).

TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

SHAPES = {  # the benchmark's rollout cells (BENCHMARK.json)
    "rollout-1.5b-grpo": dict(S=128, KH=2, G=6, L=28),
    "rollout-7b-d14-grpo": dict(S=64, KH=4, G=7, L=14),
}
# the hybrid cell's 4 attention layers: 8 KV heads of 64 padded to 128 lanes
WRITE_SHAPES = {**SHAPES, "rollout-granite-h-micro-grpo": dict(S=64, KH=8, G=4, L=4)}
# 4 attention layers of 30 KV heads, one query row each; a pool of 490 pages is 1.9 GB
ATTN_SHAPES = {**SHAPES, "rollout-olmo-hybrid-7b-d16-grpo": dict(S=64, KH=30, G=1, L=4, pages=490)}
HD, PSZ, WP, LIVE = 128, 128, 32, 0.36
STEPS = 32  # decode steps a chunk program (the cells' ``steps_per_call``)
HBM_BYTES_S = 819e9  # TPU v5e, as benchmarks/chip/benchlib/peaks.py


def draw_lengths(S: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    prompt = np.exp(rng.uniform(np.log(128), np.log(1024), S))
    out = np.clip(rng.lognormal(np.log(384), 1.0, S), 16, 3072)
    lengths = np.minimum(prompt + rng.uniform(0, 1, S) * out, 4000).astype(np.int32)
    lengths[rng.permutation(S)[round(LIVE * S):]] = 0
    return lengths


def probe(name: str, *, seed: int, reps: int, ppcb: int) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import decode_schedule, paged_attention_stacked

    S, KH, G, L = (ATTN_SHAPES[name][k] for k in ("S", "KH", "G", "L"))
    pages = ATTN_SHAPES[name].get("pages", 1200)
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (S, KH * G, HD), jnp.bfloat16)
    k = jax.random.normal(kk, (L, KH, pages, PSZ, HD), jnp.bfloat16)
    v = jax.random.normal(kv, (L, KH, pages, PSZ, HD), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.integers(1, pages, (S, WP)), jnp.int32)

    @jax.jit
    def step(q, k, v, lengths, table):
        schedule = decode_schedule(lengths, WP, PSZ, ppcb)  # once a step

        def layer(acc, li):
            out = paged_attention_stacked(
                q, k, v, li, lengths, table, pages_per_compute_block=ppcb, schedule=schedule
            )
            return acc + out.astype(jnp.float32), None

        acc, _ = jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32), jnp.arange(L))
        return acc

    mix = draw_lengths(S, seed)
    sets = {
        "mix": mix,
        "empty": np.zeros_like(mix),
        "mix512": np.where(mix > 0, -(-mix // 512) * 512, 0).astype(np.int32),
    }
    res = {"shape": name, "live_slots": int((mix > 0).sum()), "cached_tokens": int(mix.sum())}
    for label, lengths in sets.items():
        lengths = jnp.asarray(lengths)
        step(q, k, v, lengths, table).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(q, k, v, lengths, table)
        out.block_until_ready()
        res[f"{label}_us"] = (time.perf_counter() - t0) / (reps * L) * 1e6
    for label in ("mix", "mix512"):
        floor_us = 2 * KH * HD * 2 * int(sets[label].sum()) / HBM_BYTES_S * 1e6
        res[f"{label}_roofline_pct"] = 100 * floor_us / res[f"{label}_us"]
        res[f"{label}_items"] = items = int((-(-sets[label] // (ppcb * PSZ))).sum())
        res[f"{label}_us_an_item_over_bytes"] = (res[f"{label}_us"] - floor_us) / items
    return res


# 24 of 64 slots live, 8 samples a prompt of 4k-16k tokens, outputs to 3,072: the cross layers' read of layer 17's pages
LONG = "rollout-phi-4-mini-flash-longctx-grpo"
GROUP_SHAPES = {
    **{name: dict(shape, wp=WP, prompt=(128, 1024), live=LIVE) for name, shape in ATTN_SHAPES.items()},
    LONG: dict(S=64, KH=10, G=4, L=1, wp=160, prompt=(4096, 16384), live=24 / 64, q_dtype="float32", sm_scale=0.125),
}


def group_table(shape: dict, group: int, seed: int) -> tuple[np.ndarray, np.ndarray, int]:
    """(lengths [S], page table [S, wp], pages used + 1) of a batch whose
    live slots are groups of ``group`` samples of one prompt: every member's
    row holds the first one's full prompt pages and pages of its own from
    there, as ``SlotCache.alias`` leaves them."""
    S, wp = shape["S"], shape["wp"]
    rng = np.random.default_rng(seed)
    live = rng.permutation(S)[: round(shape["live"] * S) // group * group]
    lengths, table = np.zeros(S, np.int32), np.zeros((S, wp), np.int32)
    free = 1
    for members in live.reshape(-1, group):  # a group: one prompt, the first member's full prompt pages in every row
        prompt = int(np.exp(rng.uniform(*np.log(shape["prompt"]))))
        for b in members:
            out = np.clip(rng.lognormal(np.log(384), 1.0), 16, 3072) * rng.uniform(0, 1)
            lengths[b] = min(prompt + 1 + int(out), wp * PSZ - 1)
            shared = 0 if b == members[0] else prompt // PSZ
            table[b, :shared] = table[members[0], :shared]
            n = -(-int(lengths[b]) // PSZ) - shared
            table[b, shared : shared + n] = np.arange(free, free + n)
            free += n
    return lengths, table, free


def probe_group(name: str, group: int, *, seed: int, reps: int, ppcb: int) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import decode_schedule, paged_attention_stacked, shared_decode_schedule

    shape = GROUP_SHAPES[name]
    S, KH, G, L, wp = (shape[k] for k in ("S", "KH", "G", "L", "wp"))
    lengths, table, free = group_table(shape, group, seed)
    live = np.flatnonzero(lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (S, KH * G, HD), jnp.dtype(shape.get("q_dtype", "bfloat16")))
    k = jax.random.normal(kk, (L, KH, free, PSZ, HD), jnp.bfloat16)
    v = jax.random.normal(kv, (L, KH, free, PSZ, HD), jnp.bfloat16)
    lengths, table = jnp.asarray(lengths), jnp.asarray(table)

    def lists(lengths, table):
        return {"shared": shared_decode_schedule(lengths, table, PSZ, ppcb)[0], "a_slot": decode_schedule(lengths, wp, PSZ, ppcb)}

    def step(which, q, k, v, lengths, table):
        schedule = lists(lengths, table)[which]  # once a step

        def layer(acc, li):
            out = paged_attention_stacked(  # the queries differ a launch: one pool read four times is four launches
                q * (1 + li).astype(q.dtype), k, v, li % L, lengths, table,
                pages_per_compute_block=ppcb, schedule=schedule, sm_scale=shape.get("sm_scale"),
            )
            return acc + out.astype(jnp.float32), None

        return jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32), jnp.arange(max(L, 4)))[0]

    _, fetch = jax.jit(lambda le, t: shared_decode_schedule(le, t, PSZ, ppcb))(lengths, table)
    res = {
        "shape": name, "group": group, "live_slots": int(live.size), "cached_tokens": int(lengths.sum()),
        "blocks_listed": int(fetch.blocks_listed), "blocks_fetched": int(fetch.blocks), "tokens_fetched": int(fetch.tokens),
    }
    outs = {}
    steps = {"shared": jax.jit(functools.partial(step, "shared")), "a_slot": jax.jit(functools.partial(step, "a_slot"))}
    for which, fn in steps.items():
        outs[which] = fn(q, k, v, lengths, table).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q, k, v, lengths, table)
        out.block_until_ready()
        res[f"{which}_us"] = us = (time.perf_counter() - t0) / (reps * max(L, 4)) * 1e6
        tokens = res["tokens_fetched"] if which == "shared" else res["cached_tokens"]
        res[f"{which}_roofline_pct"] = 100 * (2 * KH * HD * 2 * tokens / HBM_BYTES_S * 1e6) / us
    res["max_abs_diff"] = float(jnp.max(jnp.abs(outs["shared"] - outs["a_slot"])))
    def many(which, lengths, table):  # the list 64 times in one program, every time of other lengths: no call's cost in it
        def once(acc, i):
            made = lists(jnp.where(lengths > 0, lengths + i, 0), table)[which]
            return acc + sum(a.sum() for a in jax.tree.leaves(made)), None

        return jax.lax.scan(once, jnp.int32(0), jnp.arange(64, dtype=jnp.int32))[0]

    makers = {"shared": jax.jit(functools.partial(many, "shared")), "a_slot": jax.jit(functools.partial(many, "a_slot"))}
    for which, fn in makers.items():
        fn(lengths, table).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(lengths, table)
        out.block_until_ready()
        res[f"{which}_list_us"] = (time.perf_counter() - t0) / (reps * 64) * 1e6
    return res


LATENT = dict(S=64, H=32, L=48, lanes=640, row=576, value=512)  # rollout-kanana-2-30b-a3b-ep8-grpo
FLOPS_S = 197e12  # TPU v5e bf16, as benchmarks/chip/benchlib/peaks.py


def probe_latent(*, seed: int, reps: int, ppcb: int, pages: int = 409) -> list[dict]:
    """us a launch of ``paged_latent_attn`` and of the latent row's write, by
    live slots and cached tokens, and the launch's share of its roofline."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_attention_q8 import decode_schedule, live_order
    from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked

    S, H, L, lanes, row, value = (LATENT[k] for k in ("S", "H", "L", "lanes", "row", "value"))
    kq, kp, kr = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (S, H, lanes), jnp.bfloat16)
    pool = jax.random.normal(kp, (L, 1, pages, PSZ, lanes), jnp.bfloat16)
    new_rows = jax.random.normal(kr, (L, S, 1, lanes), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.integers(1, pages, (S, WP)), jnp.int32)

    @jax.jit
    def attend(q, pool, lengths, table):
        schedule = decode_schedule(lengths, WP, PSZ, ppcb)  # once a step

        def layer(acc, li):
            out = paged_latent_attention_stacked(
                q, pool, li, lengths, table, value_lanes=value, pages_per_compute_block=ppcb, schedule=schedule, sm_scale=192**-0.5
            )
            return acc + out, None

        return jax.lax.scan(layer, jnp.zeros((S, H, value), jnp.float32), jnp.arange(L))[0]

    def write(pool, rows, lengths, table):
        live = live_order(lengths > 0)
        slot = jnp.arange(S)
        page, off = table[slot, lengths // PSZ], lengths % PSZ

        def layer(c, xs):
            li, r = xs
            return paged_kv.write_decode_rows(c, li, r, None, page, off, live), None

        return jax.lax.scan(layer, {"k": pool}, (jnp.arange(L, dtype=jnp.int32), rows))[0]["k"]

    write = jax.jit(write, donate_argnums=0)
    mix = draw_lengths(S, seed)
    sets = {"mix": mix, "empty": np.zeros_like(mix)}
    # 300 and 600: a slot's last block holds 3 pages or 1, so the copies are short and the item's own time shows
    for n_live, sizes in ((16, (1024, 4000)), (24, (300, 600, 1024, 4000)), (48, (1024, 4000))):
        for tokens in sizes:
            lengths = np.zeros(S, np.int32)
            lengths[rng.permutation(S)[:n_live]] = tokens
            sets[f"{n_live}x{tokens}"] = lengths
    out = []
    for label, lengths in sets.items():
        cached = int(lengths.sum())
        lengths = jnp.asarray(lengths)
        attend(q, pool, lengths, table).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            o = attend(q, pool, lengths, table)
        o.block_until_ready()
        us = (time.perf_counter() - t0) / (reps * L) * 1e6
        pool = write(pool, new_rows, lengths, table)
        t0 = time.perf_counter()
        for _ in range(reps):
            pool = write(pool, new_rows, lengths, table)
        pool.block_until_ready()
        write_us = (time.perf_counter() - t0) / (reps * L) * 1e6
        floor_us = max(row * 2 * cached / HBM_BYTES_S, 2 * H * (row + value) * cached / FLOPS_S) * 1e6
        out.append({
            "kernel": "paged_latent_attn", "lengths": label, "live_slots": int((np.asarray(lengths) > 0).sum()), "cached_tokens": cached,
            "us": us, "roofline_pct": 100 * floor_us / us if cached else None, "stored_bytes_pct": 100 * lanes * 2 * cached / HBM_BYTES_S * 1e6 / us,
            "row_write_us": write_us,
        })
    return out


# the long-context cells' table (rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo, 32 heads; 64: a GLM-5 without its index)
LATENT_LONG = dict(S=64, L=4, wp=160, prompt=(4096, 16384), live=24 / 64, lanes=640, row=576, value=512)


def probe_latent_group(H: int, group: int, *, seed: int, reps: int, ppcb: int) -> dict:
    """us a launch of ``paged_latent_attn`` over a table whose live slots are
    groups of ``group`` samples of one prompt: over the list that names each
    distinct block once (its readers' query rows stacked) and over one item a
    (slot, block), each with its share of the time of the bytes IT fetches
    (the published row's), and the largest difference between the outputs."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import DecodeItems, decode_schedule, shared_decode_schedule
    from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked

    shape = LATENT_LONG
    S, L, wp, lanes, row, value = (shape[k] for k in ("S", "L", "wp", "lanes", "row", "value"))
    lengths, table, free = group_table(shape, group, seed)
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (S, H, lanes), jnp.bfloat16)
    pool = jax.random.normal(kp, (L, 1, free, PSZ, lanes), jnp.bfloat16)
    lengths, table = jnp.asarray(lengths), jnp.asarray(table)

    def lists(lengths, table):
        return {
            "shared": shared_decode_schedule(lengths, table, PSZ, ppcb)[0],
            "a_slot": DecodeItems.private(decode_schedule(lengths, wp, PSZ, ppcb)),
        }

    def step(which, q, pool, lengths, table):
        schedule = lists(lengths, table)[which]  # once a step

        def layer(acc, li):
            out = paged_latent_attention_stacked(
                q * (1 + li).astype(q.dtype), pool, li % L, lengths, table,
                value_lanes=value, pages_per_compute_block=ppcb, schedule=schedule, sm_scale=192**-0.5,
            )
            return acc + out, None

        return jax.lax.scan(layer, jnp.zeros((S, H, value), jnp.float32), jnp.arange(2 * L))[0]

    _, fetch = jax.jit(lambda le, t: shared_decode_schedule(le, t, PSZ, ppcb))(lengths, table)
    res = {
        "kernel": "paged_latent_attn", "heads": H, "group": group, "live_slots": int((np.asarray(lengths) > 0).sum()),
        "cached_tokens": int(lengths.sum()), "blocks_listed": int(fetch.blocks_listed), "blocks_fetched": int(fetch.blocks),
        "tokens_fetched": int(fetch.tokens),
    }
    outs = {}
    steps = {"shared": jax.jit(functools.partial(step, "shared")), "a_slot": jax.jit(functools.partial(step, "a_slot"))}
    for which, fn in steps.items():
        outs[which] = fn(q, pool, lengths, table).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q, pool, lengths, table)
        out.block_until_ready()
        res[f"{which}_us"] = us = (time.perf_counter() - t0) / (reps * 2 * L) * 1e6
        tokens = res["tokens_fetched"] if which == "shared" else res["cached_tokens"]
        res[f"{which}_fetched_bytes_pct"] = 100 * (row * 2 * tokens / HBM_BYTES_S * 1e6) / us
    res["max_abs_diff"] = float(jnp.max(jnp.abs(outs["shared"] - outs["a_slot"])))
    return res


DSA = dict(S=64, H=64, L=6, lanes=640, row=576, value=512, Hi=32, d=128, topk=2048, WP=160)  # rollout-glm-5-ep16-d6-longctx-grpo


def probe_dsa(*, seed: int, reps: int, ppcb: int, pages: int = 2730) -> list[dict]:
    """us a layer of each piece of a decode step's sparse read, by cached tokens a slot."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid
    from areal_tpu.ops.paged_attention_q8 import decode_schedule
    from areal_tpu.ops.paged_latent_attention import paged_index_scores_stacked, paged_latent_attention_stacked

    S, H, L, lanes, row, value, Hi, d, topk, wp = (DSA[k] for k in ("S", "H", "L", "lanes", "row", "value", "Hi", "d", "topk", "WP"))
    W = wp * PSZ
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (S, H, lanes), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (L, 1, pages, PSZ, lanes), jnp.bfloat16)
    idx = jax.random.normal(ks[2], (L, 1, pages, PSZ, d), jnp.bfloat16)
    q_i = jax.random.normal(ks[3], (S, Hi, d), jnp.bfloat16)
    w_i = jax.random.normal(ks[4], (S, Hi), jnp.float32)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.integers(1, pages, (S, wp)), jnp.int32)
    cols = jnp.arange(W, dtype=jnp.int32)[None, :]

    def over_layers(fn, init):
        return jax.lax.scan(lambda acc, li: (acc + fn(li), None), init, jnp.arange(L, dtype=jnp.int32))[0]

    # the pools ride in as arguments: closed over they would be constants of the program (2.7 GB of them)
    @jax.jit
    def score(idx, lengths):
        sched = decode_schedule(lengths, wp, PSZ, ppcb)
        return over_layers(lambda li: paged_index_scores_stacked(q_i, w_i, idx, li, lengths, table, pages_per_compute_block=ppcb, schedule=sched), jnp.zeros((S, W), jnp.float32))

    @jax.jit
    def select_bits(scores, lengths):
        return over_layers(lambda li: hybrid.select_top(scores + li, cols < lengths[:, None], topk).astype(jnp.int32), jnp.zeros((S, W), jnp.int32))

    @jax.jit
    def select_sort(scores, lengths):
        def one(li):
            return jax.lax.top_k(jnp.where(cols < lengths[:, None], scores + li, -jnp.inf), topk)[1]

        return over_layers(one, jnp.zeros((S, topk), jnp.int32))

    @jax.jit
    def read_masked(pool, lengths, chosen):
        sched = decode_schedule(lengths, wp, PSZ, ppcb)
        return over_layers(
            lambda li: paged_latent_attention_stacked(
                q, pool, li, lengths, table, value_lanes=value, pages_per_compute_block=ppcb, schedule=sched, sm_scale=256**-0.5, select=chosen
            ),
            jnp.zeros((S, H, value), jnp.float32),
        )

    @jax.jit
    def read_gathered(pool, lengths, picks):
        """The selected rows by index (``picks`` [S, topk] positions, the first min(topk, cached) valid)."""
        flat = jnp.take_along_axis(table, picks // PSZ, axis=1) * PSZ + picks % PSZ  # [S, topk] rows of the pool
        valid = jnp.arange(topk)[None, :] < jnp.minimum(lengths, topk)[:, None]

        def one(li):
            rows = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)[0].reshape(pages * PSZ, lanes)[flat]  # [S, topk, lanes]
            logits = jnp.einsum("shl,stl->sht", q, rows, preferred_element_type=jnp.float32) * 256**-0.5
            p = jax.nn.softmax(jnp.where(valid[:, None, :], logits, -1e30), axis=-1)
            return jnp.einsum("sht,stv->shv", p.astype(rows.dtype), rows[..., :value], preferred_element_type=jnp.float32)

        return over_layers(one, jnp.zeros((S, H, value), jnp.float32))

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (reps * L) * 1e6

    out = []
    mix = np.exp(rng.uniform(np.log(4096), np.log(16384), S)).astype(np.int32) + rng.integers(0, 1500, S).astype(np.int32)
    for label, lengths in (("mix", mix), ("4608", np.full(S, 4608, np.int32)), ("9600", np.full(S, 9600, np.int32)), ("19200", np.full(S, 19200, np.int32)), ("40of64-mix", np.where(np.arange(S) < 40, mix, 0).astype(np.int32))):
        cached = int(lengths.sum())
        selected = int(np.minimum(lengths, topk).sum())
        lengths = jnp.asarray(lengths)
        scores = score(idx, lengths) / L
        chosen = hybrid.select_top(scores, cols < lengths[:, None], topk)
        picks = jax.lax.top_k(jnp.where(cols < lengths[:, None], scores, -jnp.inf), topk)[1]
        masked, gathered = read_masked(pool, lengths, chosen), read_gathered(pool, lengths, picks)
        res = {
            "probe": "dsa", "lengths": label, "live_slots": int((np.asarray(lengths) > 0).sum()), "cached_tokens": cached, "selected_tokens": selected,
            "index_score_us": timed(score, idx, lengths), "select_bits_us": timed(select_bits, scores, lengths), "select_top_k_us": timed(select_sort, scores, lengths),
            "read_masked_us": timed(read_masked, pool, lengths, chosen), "read_gathered_us": timed(read_gathered, pool, lengths, picks),
            "read_all_rows_us": timed(read_masked, pool, lengths, None),
            "masked_vs_gathered_max_abs": float(jnp.max(jnp.abs(masked - gathered))),
        }
        res["index_roofline_pct"] = 100 * d * 2 * cached / HBM_BYTES_S * 1e6 / res["index_score_us"]
        res["masked_selected_roofline_pct"] = 100 * row * 2 * selected / HBM_BYTES_S * 1e6 / res["read_masked_us"]
        res["gathered_selected_roofline_pct"] = 100 * row * 2 * selected / HBM_BYTES_S * 1e6 / res["read_gathered_us"]
        out.append(res)
    return out


PREFILL_HEADS = {32: dict(dn=128, dr=64, dv=128), 64: dict(dn=192, dr=64, dv=256)}  # the kanana cell's heads, the GLM-5 cell's
PREFILL_LAUNCH_BLOCKS = ((256, 512), (512, 512), (512, 1024), (1024, 512), (1024, 1024), (512, 2048), (1024, 2048))


def _prefill_attention(cfg, L: int, blocks: tuple[int, int], launch: bool):
    """The jitted attention of one prompt: ``hybrid.prefill_attend_block`` over every query block."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    tq = blocks[0]

    def attention(qn, qr, kv, k_r, chosen):
        def block(i):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(a, i * tq, tq, axis=0)  # noqa: E731
            o = hybrid.prefill_attend_block(cfg, rows(qn), rows(qr), kv, k_r, i, blocks, None if chosen is None else rows(chosen), launch)
            # behind a barrier, as the output projection stands behind it in the model: written straight into the map's
            # stacked result the launch is wrapped in a fusion that drops its VMEM limit
            return jax.lax.optimization_barrier(o)

        return jax.lax.map(block, jnp.arange(L // tq, dtype=jnp.int32)).reshape(L, -1)

    return jax.jit(attention)


def probe_latent_prefill(H: int, L: int, *, seed: int, reps: int, topk: int = 2048) -> list[dict]:
    """us a layer and TFLOP/s of the attention of one prompt of ``L`` tokens at ``H`` heads, the XLA loop and
    the launch by its blocks, causal and under a selection of ``topk`` keys a query."""
    import types

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid
    from areal_tpu.ops.latent_prefill_attention import padded_w_kvb

    dn, dr, dv = (PREFILL_HEADS[H][k] for k in ("dn", "dr", "dv"))
    ks = jax.random.split(jax.random.PRNGKey(seed + L + H), 6)
    c = jax.random.normal(ks[0], (L, 512), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (512, H * (dn + dv)), jnp.float32) * 512**-0.5).astype(jnp.bfloat16)
    qn = jax.random.normal(ks[2], (L, H, dn), jnp.bfloat16)
    qr = jax.random.normal(ks[3], (L, H, dr), jnp.bfloat16)
    k_r = jax.random.normal(ks[4], (L, dr), jnp.bfloat16)
    kv = {False: jax.jit(lambda c, w: (c @ w).reshape(L, H, dn + dv))(c, w), True: jax.jit(lambda c, w: c @ padded_w_kvb(w, H, dn, dr))(c, w)}
    pos = jnp.arange(L, dtype=jnp.int32)
    # about min(topk, visible) keys a query, its own among them
    drawn = jax.jit(lambda k: (jax.random.uniform(k, (L, L)) * (pos[:, None] + 1) < topk) & (pos[:, None] >= pos[None, :]) | (pos[:, None] == pos[None, :]))(ks[5])
    flops = L * (L + 1) // 2 * H * 2 * (dn + dr + dv)

    def timed(cfg, blocks, launch, chosen):
        fn, args = _prefill_attention(cfg, L, blocks, launch), (qn, qr, kv[launch], k_r, chosen)
        got = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = fn(*args)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e6, got

    out = []
    for masked in (False, True):
        cfg = types.SimpleNamespace(num_heads=H, qk_nope_head_dim=dn, v_head_dim=dv, sm_scale=(dn + dr) ** -0.5, index_topk=topk if masked else 0)
        chosen = drawn if masked else None
        xla_blocks, chosen_blocks = hybrid.prefill_blocks(cfg, L), hybrid.prefill_blocks(cfg, L, True)
        xla_us, want = timed(cfg, xla_blocks, False, chosen)
        row = {
            "probe": "latent-prefill", "heads": H, "tokens": L, "mask_operand": masked, "xla_blocks": list(xla_blocks),
            "xla_us": xla_us, "xla_tflops": flops / xla_us / 1e6, "takes_launch": hybrid.prefill_takes_launch(cfg, L), "chosen_blocks": list(chosen_blocks), "launch": {},
        }
        for blocks in sorted({*PREFILL_LAUNCH_BLOCKS, chosen_blocks}):
            if L % blocks[0] or L % blocks[1]:
                continue
            us, got = timed(cfg, blocks, True, chosen)
            row["launch"][f"{blocks[0]}x{blocks[1]}"] = {
                "us": us, "tflops": flops / us / 1e6, "max_abs_vs_xla": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))),
            }
        out.append(row)
    return out


def probe_write(name: str, *, seed: int, reps: int, quant: bool, pages: int = 400) -> dict:
    """us a layer of a decode step's KV write, scatters against the kernel."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference import paged_kv
    from areal_tpu.ops.paged_attention_q8 import live_order

    S, KH, L = (WRITE_SHAPES[name][k] for k in ("S", "KH", "L"))
    rng = np.random.default_rng(seed)
    write_page = jnp.asarray(1 + rng.permutation(pages - 1)[:S], jnp.int32)  # a page of its own a slot
    write_off = jnp.asarray(rng.integers(0, PSZ, S), jnp.int32)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    k = jax.random.normal(kk, (L, S, KH, HD), jnp.bfloat16)
    v = jax.random.normal(kv, (L, S, KH, HD), jnp.bfloat16)

    @jax.jit
    def fresh():
        """Pools in which no two tiles are alike, whatever their layer, head,
        page or rows: a tile that goes back stale, or to another place, shows."""
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
        shape = (L, KH, pages, PSZ, HD)
        if not quant:
            return {n: jax.random.normal(key, shape, jnp.bfloat16) for n, key in zip("kv", keys)}
        cache = {n: jax.random.randint(key, shape, -127, 128, jnp.int8) for n, key in zip("kv", keys)}
        for n, key in zip("kv", keys[2:]):
            cache[f"{n}_scale"] = jax.random.uniform(key, (L, KH, pages, 1, PSZ), jnp.float32, 0.5, 1.5)
        return cache

    def chunk(cache, k, v, table_head, kernel):
        """``STEPS`` decode steps' writes in one program, as the engine's chunk
        program makes them (a call's dispatch is 0.25 ms on the host)."""
        page = jnp.where(table_head == 0, 0, write_page)  # an ended slot's table row is the trash page

        def step(c, _):
            live = live_order(table_head != 0) if kernel else None  # once a step

            def layer(c, xs):
                li, kl, vl = xs
                return paged_kv.write_decode_rows(c, li, kl, vl, page, write_off, live), None

            return jax.lax.scan(layer, c, (jnp.arange(L, dtype=jnp.int32), k, v))[0], None

        return jax.lax.scan(step, cache, None, length=STEPS)[0]

    steps = {
        "scatter": jax.jit(lambda c, k, v, t: chunk(c, k, v, t, False), donate_argnums=0),
        "kernel": jax.jit(lambda c, k, v, t: chunk(c, k, v, t, True), donate_argnums=0),
    }
    same = jax.jit(lambda a, b: jnp.array_equal(a[:, :, 1:], b[:, :, 1:]))  # every page but the trash page
    res = {"shape": name, "pages": "int8" if quant else "bf16", "slots": S, "kv_heads": KH, "layers": L}
    for share in (0.0, 0.25, 0.5, 1.0):
        head = np.zeros(S, np.int32)
        head[rng.permutation(S)[: round(share * S)]] = 1
        head = jnp.asarray(head)
        caches = {label: fn(fresh(), k, v, head) for label, fn in steps.items()}
        res[f"same_bits_{int(100 * share)}"] = all(bool(same(caches["scatter"][n], caches["kernel"][n])) for n in caches["scatter"])
        for label, fn in steps.items():
            cache = caches.pop(label)
            t0 = time.perf_counter()
            for _ in range(reps):
                cache = fn(cache, k, v, head)
            jax.block_until_ready(cache)
            res[f"{label}_us_{int(100 * share)}"] = (time.perf_counter() - t0) / (reps * STEPS * L) * 1e6
            del cache
    return res


def main() -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ppcb", type=int, default=4, help="pages a compute block (the decode step's choice at this table: 4)")
    ap.add_argument("--only", choices=("latent", "dsa", "latent-prefill"), help="the latent-attention cell's launches alone, the sparse read's pieces, or the prompt pass's attention")
    ap.add_argument("--group", help="readers a prompt, e.g. 1,2,4,8: paged_decode_attn alone (with --only latent: paged_latent_attn) over a table whose live slots are such groups")
    ap.add_argument("--tokens", default="1024,4096,8192,16384", help="latent-prefill: the prompt lengths")
    ap.add_argument("--heads", default="32,64", help="latent-prefill, and latent with --group: 32 (the kanana and xing4 cells' heads) and / or 64 (the GLM-5 cell's)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("decode_attn_probe: needs a TPU (a CPU time is no speed)")
        return 2
    if args.group and args.only == "latent":
        for H in args.heads.split(","):
            for group in args.group.split(","):
                print(json.dumps(probe_latent_group(int(H), int(group), seed=args.seed, reps=args.reps, ppcb=args.ppcb)), flush=True)
        return 0
    if args.group:
        for name in GROUP_SHAPES:
            for group in args.group.split(","):
                print(json.dumps(probe_group(name, int(group), seed=args.seed, reps=args.reps, ppcb=args.ppcb)), flush=True)
        return 0
    if args.only == "latent-prefill":
        for H in args.heads.split(","):
            for L in args.tokens.split(","):
                for res in probe_latent_prefill(int(H), int(L), seed=args.seed, reps=args.reps):
                    print(json.dumps(res), flush=True)
        return 0
    if args.only == "dsa":
        for res in probe_dsa(seed=args.seed, reps=args.reps, ppcb=args.ppcb):
            print(json.dumps(res), flush=True)
        return 0
    for res in probe_latent(seed=args.seed, reps=args.reps, ppcb=args.ppcb):
        print(json.dumps(res), flush=True)
    if args.only:
        return 0
    for name in ATTN_SHAPES:
        print(json.dumps(probe(name, seed=args.seed, reps=args.reps, ppcb=args.ppcb)), flush=True)
    for name in WRITE_SHAPES:
        for quant in (False, True):
            print(json.dumps(probe_write(name, seed=args.seed, reps=args.reps, quant=quant)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
