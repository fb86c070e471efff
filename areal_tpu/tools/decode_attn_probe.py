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
share of the chip's memory roofline.

``paged_latent_attn`` (``--only latent`` runs it alone): the latent-attention
cell's launch, 32 query rows of 640 lanes over ONE stacked pool of 1,280 B
rows (576 published values in 640 lanes), 48 layers, by live slots (16 / 24 /
48 of 64) and by cached tokens (the ``mix`` lengths, and every live slot at
1k / 4k), against the bytes and operations of the PUBLISHED row (1,152 B and
69,632 operations a cached token and layer), and the row write of the same
pool (one ``paged_kv_write`` launch a layer over the live slots).

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
import json
import time

import numpy as np

SHAPES = {  # the benchmark's rollout cells (BENCHMARK.json)
    "rollout-1.5b-grpo": dict(S=128, KH=2, G=6, L=28),
    "rollout-7b-d14-grpo": dict(S=64, KH=4, G=7, L=14),
}
# the hybrid cell's 4 attention layers: 8 KV heads of 64 padded to 128 lanes
WRITE_SHAPES = {**SHAPES, "rollout-granite-h-micro-grpo": dict(S=64, KH=8, G=4, L=4)}
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


def probe(name: str, *, seed: int, reps: int, ppcb: int, pages: int = 1200) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import decode_schedule, paged_attention_stacked

    S, KH, G, L = (SHAPES[name][k] for k in ("S", "KH", "G", "L"))
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
    floor_us = 2 * KH * HD * 2 * int(mix.sum()) / HBM_BYTES_S * 1e6
    res["mix_roofline_pct"] = 100 * floor_us / res["mix_us"]
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
    for n_live in (16, 24, 48):
        for tokens in (1024, 4000):
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
    ap.add_argument("--only", choices=("latent",), help="the latent-attention cell's launches alone")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("decode_attn_probe: needs a TPU (a CPU time is no speed)")
        return 2
    for res in probe_latent(seed=args.seed, reps=args.reps, ppcb=args.ppcb):
        print(json.dumps(res), flush=True)
    if args.only:
        return 0
    for name in SHAPES:
        print(json.dumps(probe(name, seed=args.seed, reps=args.reps, ppcb=args.ppcb)), flush=True)
    for name in WRITE_SHAPES:
        for quant in (False, True):
            print(json.dumps(probe_write(name, seed=args.seed, reps=args.reps, quant=quant)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
