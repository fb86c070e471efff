"""Time ``paged_decode_attn`` alone on the chip at the rollout cells' shapes.

    chiprun -- python -m areal_tpu.tools.decode_attn_probe

One launch per layer over a stacked bf16 pool, as the decode step makes
them (128-token pages, a 32-page table), for three sets of lengths:

  ``mix``     36% of the slots live, lengths drawn like the benchmark's
              ``grpo-reasoning`` traffic (prompt log-uniform 128-1024 plus a
              uniform share of a lognormal output, median 384)
  ``empty``   no slot live: the launch's fixed cost
  ``mix512``  the same live set, every length rounded up to 512: what whole
              512-token blocks cost against ``mix``

and prints microseconds a launch, the KV bytes the lengths need and their
share of the chip's memory roofline. TPU only: a CPU time is no speed.
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
HD, PSZ, WP, LIVE = 128, 128, 32, 0.36
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


def main() -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ppcb", type=int, default=4, help="pages a compute block (the decode step's choice at this table: 4)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("decode_attn_probe: needs a TPU (a CPU time is no speed)")
        return 2
    for name in SHAPES:
        print(json.dumps(probe(name, seed=args.seed, reps=args.reps, ppcb=args.ppcb)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
