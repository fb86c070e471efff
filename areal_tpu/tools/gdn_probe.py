"""Time one gated-delta-rule layer alone on the chip at the published sizes of
the benchmark's ``rollout-olmo-hybrid-7b-d16-grpo`` (30 heads of 96 x 192, a
float32 state of 2,211,840 B a slot and layer, 64 slots, 12 layers).

    chiprun -- python -m areal_tpu.tools.gdn_probe

``state``: the decode step's state kernel (``ops/gdn_state_update.py``) with
8 / 23 / 64 of the 64 slots live, one launch a layer over 12 stacked layers as
a serving program runs it, in microseconds a layer, against the bytes it has
to move (the live slots' state read and written, 819 GB/s); beside it the
masked XLA form over all slots (``hybrid.gdn_decode_step``), and the largest
difference between the two on the live slots (the compiled kernel's own
check: interpret mode on the CPU cannot see what the chip's compiler does).
``scan``: the prefill's chunked scan (``hybrid.gdn_chunked_scan``) of one
layer at 256 / 1,024 rows, in microseconds, and its largest difference from
the token-by-token recurrence on the final state.
TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import json
import time

H, K, V, SLOTS, LAYERS = 30, 96, 192, 64, 12
HBM_BYTES_S = 819e9  # TPU v5e, as benchmarks/chip/benchlib/peaks.py


def _timed(fn, *args, reps: int):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def _inputs(key, lead):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (*lead, H, K))) * K**-0.5
    k = unit(jax.random.normal(ks[1], (*lead, H, K)))
    v = jax.random.normal(ks[2], (*lead, H, V))
    g = -jax.random.uniform(ks[3], (*lead, H)) * jnp.exp(jax.random.uniform(ks[4], (H,), minval=-7.0, maxval=1.0))
    beta = 2.0 * jax.random.uniform(ks[5], (*lead, H))
    return q, k, v, g, beta


def probe_state(n_live: int, reps: int, seed: int, dtype: str) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid
    from areal_tpu.ops import gdn_state_update as gsu
    from areal_tpu.ops.paged_attention_q8 import live_order

    p = gsu.head_pack(H, V)
    key = jax.random.PRNGKey(seed)
    state = gsu.pack_state(0.1 * jax.random.normal(key, (LAYERS, SLOTS, H, K, V), jnp.float32), p).astype(dtype)
    q, k, v, g, beta = _inputs(jax.random.fold_in(key, 1), (SLOTS,))
    active = jnp.zeros((SLOTS,), bool).at[jax.random.permutation(jax.random.fold_in(key, 2), SLOTS)[:n_live]].set(True)
    order, n = live_order(active)

    def kernel(state):
        def layer(j, c):
            st, acc = c
            st, o = gsu.gdn_state_update_stacked(st, j, q, k, v, jnp.exp(g), beta, order, n)
            return st, acc + o

        return jax.lax.fori_loop(0, LAYERS, layer, (state, jnp.zeros((SLOTS, H, V), jnp.float32)))

    def masked(state):
        def layer(j, c):
            st, acc = c
            old = jax.lax.dynamic_index_in_dim(st, j, 0, keepdims=False)
            new, o = hybrid.gdn_decode_step(gsu.unpack_state(old, p), q, k, v, jnp.exp(g), beta, active)
            return jax.lax.dynamic_update_index_in_dim(st, gsu.pack_state(new, p), j, 0), acc + o

        return jax.lax.fori_loop(0, LAYERS, layer, (state, jnp.zeros((SLOTS, H, V), jnp.float32)))

    # not donated: every repetition starts from the same state (one copy of it more in the time, both forms alike)
    t_k, (s_k, o_k) = _timed(jax.jit(kernel), state, reps=reps)
    t_m, (s_m, o_m) = _timed(jax.jit(masked), state, reps=reps)
    t_copy, _ = _timed(jax.jit(lambda s: s + 0), state, reps=reps)
    live = jnp.where(active)[0]
    err_s = float(jnp.max(jnp.abs(s_k[:, live].astype(jnp.float32) - s_m[:, live].astype(jnp.float32))))
    dead_same = bool(jnp.array_equal(s_k[:, ~active], state[:, ~active]))
    least = n_live * 2 * H * K * V * jnp.dtype(dtype).itemsize / HBM_BYTES_S
    us = lambda t: round((t - t_copy) / LAYERS * 1e6, 1)  # noqa: E731
    return {
        "probe": "state", "state_dtype": dtype, "live": n_live, "of": SLOTS,
        "kernel_us_a_layer": us(t_k), "masked_xla_us_a_layer": us(t_m), "copy_of_the_state_us": round(t_copy * 1e6, 1),
        "least_us_a_layer": round(least * 1e6, 1), "kernel_roofline_pct": round(100 * least / ((t_k - t_copy) / LAYERS), 1),
        "max_abs_state_diff": err_s, "max_abs_o_diff": float(jnp.max(jnp.abs(o_k[live] - o_m[live]))), "dead_slots_bit_for_bit": dead_same,
    }


def probe_scan(rows: int, reps: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    q, k, v, g, beta = _inputs(jax.random.PRNGKey(seed), (1, rows))
    n_state = jnp.asarray([rows], jnp.int32)
    scan = jax.jit(lambda *a: hybrid.gdn_chunked_scan(*a, n_state))

    def loop(q, k, v, g, beta):
        def tok(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            return hybrid.gdn_decode_step(s, q_t, k_t, v_t, jnp.exp(g_t), b_t, jnp.ones((1,), bool))

        return jax.lax.scan(tok, jnp.zeros((1, H, K, V), jnp.float32), tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))

    t_s, (s_c, o_c) = _timed(scan, q, k, v, g, beta, reps=reps)
    t_l, (s_l, o_l) = _timed(jax.jit(loop), q, k, v, g, beta, reps=max(1, reps // 4))
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))  # noqa: E731
    return {
        "probe": "scan", "rows": rows, "chunked_us": round(t_s * 1e6, 1), "token_loop_us": round(t_l * 1e6, 1),
        "state_rel_diff": rel(s_c, s_l), "o_rel_diff": rel(o_c[0], jnp.swapaxes(o_l, 0, 1)[0]),
    }


def main(argv=None) -> int:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--live", default="8,23,64")
    p.add_argument("--rows", default="256,1024")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--state-dtypes", default="float32")
    a = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("gdn_probe needs a TPU: a CPU time is no speed")
        return 2
    for dtype in a.state_dtypes.split(","):
        for n in [int(x) for x in a.live.split(",") if x]:
            print(json.dumps(probe_state(n, a.reps, a.seed, dtype)), flush=True)
    for rows in [int(x) for x in a.rows.split(",") if x]:
        print(json.dumps(probe_scan(rows, a.reps, a.seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
