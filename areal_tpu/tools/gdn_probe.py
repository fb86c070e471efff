"""Time the recurrent mixers' decode-step state kernels alone on the chip at
their cells' sizes, and one gated-delta-rule layer's prompt scan.

    chiprun -- python -m areal_tpu.tools.gdn_probe [--kernels gdn,ssm,kda]

``state``: a state kernel (``ops/{gdn,ssm,kda}_state_update.py``, all three
on the one walk of ``ops/slot_walk.py``) by name from ``STATE_KERNELS``:
``gdn`` at ``rollout-olmo-hybrid-7b-d16-grpo``'s 30 heads of 96 x 192 (a
float32 state of 2,211,840 B a slot and layer, 12 layers), ``ssm`` at
``rollout-granite-h-micro-grpo``'s 64 heads of 64 x 128 (2,097,152 B; 12 of
its 36 layers), ``kda`` at ``rollout-solar-open2-ep16-d8-longctx-grpo``'s 64
heads of 128 x 128 (4,194,304 B, 6 layers), each of 64 slots. With 8 / 23 /
64 of the slots live, one launch a layer over the stacked layers as a serving
program runs it, in microseconds a layer, against the bytes it has to move
(the live slots' state read and written, 819 GB/s); beside it the masked XLA
form over all slots, and the largest difference between the two on the live
slots (the compiled kernel's own check: interpret mode on the CPU cannot see
what the chip's compiler does). After the points a ``state_fit`` line: the
least-squares microseconds a slot and a launch. ``--parent-ops DIR`` puts the
files of the same names under ``DIR`` (another checkout's ``areal_tpu/ops``)
in the masked form's place: timed in the same process, and the two kernels'
states and outputs compared bit for bit.
``scan``: the prefill's chunked scan (``hybrid.gdn_chunked_scan``) of one
layer at 256 / 1,024 rows, in microseconds, and its largest difference from
the token-by-token recurrence on the final state.
TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

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


def _unit(t):
    import jax
    import jax.numpy as jnp

    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _inputs(key, lead):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    q = _unit(jax.random.normal(ks[0], (*lead, H, K))) * K**-0.5
    k = _unit(jax.random.normal(ks[1], (*lead, H, K)))
    v = jax.random.normal(ks[2], (*lead, H, V))
    g = -jax.random.uniform(ks[3], (*lead, H)) * jnp.exp(jax.random.uniform(ks[4], (H,), minval=-7.0, maxval=1.0))
    beta = 2.0 * jax.random.uniform(ks[5], (*lead, H))
    return q, k, v, g, beta


def _kernel_module(name: str, parent_ops: str | None):
    """``ops/<name>_state_update``, or the file of that name under
    ``parent_ops`` (another checkout's ``areal_tpu/ops``) under a name of its
    own: the kernel this one is timed against and held to, bit for bit."""
    import importlib
    import importlib.util
    import os

    if parent_ops is None:
        return importlib.import_module(f"areal_tpu.ops.{name}_state_update")
    spec = importlib.util.spec_from_file_location(f"_parent_{name}_state_update", os.path.join(parent_ops, f"{name}_state_update.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gdn_case(key, dtype, mod, dims):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    assert dims == (H, K, V, LAYERS), dims  # ``_inputs`` is the scan's too
    p = mod.head_pack(H, V)
    state = mod.pack_state(0.1 * jax.random.normal(key, (LAYERS, SLOTS, H, K, V)), p).astype(dtype)
    q, k, v, g, beta = _inputs(jax.random.fold_in(key, 1), (SLOTS,))

    def launch(m, st, j, order, n):
        st, o = m.gdn_state_update_stacked(st, j, q, k, v, jnp.exp(g), beta, order, n)
        return st, o.reshape(SLOTS, -1)

    def masked(old, active):
        new, o = hybrid.gdn_decode_step(mod.unpack_state(old, p), q, k, v, jnp.exp(g), beta, active)
        return mod.pack_state(new, p), o.reshape(SLOTS, -1)

    return state, launch, masked


def _kda_case(key, dtype, mod, dims):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    heads, kd, vd, layers = dims
    ks = jax.random.split(jax.random.fold_in(key, 1), 5)
    state = (0.1 * jax.random.normal(key, (layers, SLOTS, heads, kd, vd))).astype(dtype)
    q, k = _unit(jax.random.normal(ks[0], (SLOTS, heads, kd))) * kd**-0.5, _unit(jax.random.normal(ks[1], (SLOTS, heads, kd)))
    v, beta = jax.random.normal(ks[2], (SLOTS, heads, vd)), jax.random.uniform(ks[3], (SLOTS, heads))
    decay = jnp.exp(-jax.random.uniform(ks[4], (SLOTS, heads, kd)))

    def launch(m, st, j, order, n):
        st, o = m.kda_state_update_stacked(st, j, q, k, v, decay, beta, order, n)
        return st, o.reshape(SLOTS, -1)

    def masked(old, active):
        new, o = hybrid.kda_decode_step(old, q, k, v, decay, beta, active)
        return new, o.reshape(SLOTS, -1)

    return state, launch, masked


def _ssm_case(key, dtype, mod, dims):
    import jax
    import jax.numpy as jnp

    heads, pd, nd, layers = dims  # B and C in one group
    ks = jax.random.split(jax.random.fold_in(key, 1), 5)
    state = (0.1 * jax.random.normal(key, (layers, SLOTS, heads, pd, nd))).astype(dtype)
    x, b, c = jax.random.normal(ks[0], (SLOTS, heads, pd)), jax.random.normal(ks[1], (SLOTS, 1, nd)), jax.random.normal(ks[2], (SLOTS, 1, nd))
    dt, a = jax.nn.softplus(jax.random.normal(ks[3], (SLOTS, heads))), -jnp.exp(jax.random.normal(ks[4], (heads,)))

    def launch(m, st, j, order, n):
        st, y = m.ssm_state_update_stacked(st, j, x, b, c, dt, a, order, n)
        return st, y.reshape(SLOTS, -1)

    def masked(old, active):  # ``hybrid.ssm_decode_step``'s expressions, without its configuration and its skip term
        new = old.astype(jnp.float32) * jnp.exp(dt * a)[..., None, None] + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        y = jnp.sum(new * c[:, :, None, :], axis=-1)
        return jnp.where(active[:, None, None, None], new.astype(old.dtype), old), y.reshape(SLOTS, -1)

    return state, launch, masked


# the decode step's state kernels at their cells' sizes: the cell, the case's builder, (heads, a head's tile, stacked
# layers). ``ssm`` stacks 12 of the cell's 36 layers, so that the state, two results and the masked form's temporaries
# fit the chip: a launch reads one layer whatever the stack holds.
STATE_KERNELS = {
    "gdn": ("rollout-olmo-hybrid-7b-d16-grpo", _gdn_case, (H, K, V, LAYERS)),
    "ssm": ("rollout-granite-h-micro-grpo", _ssm_case, (64, 64, 128, 12)),
    "kda": ("rollout-solar-open2-ep16-d8-longctx-grpo", _kda_case, (64, 128, 128, 6)),
}


def probe_state(kernel: str, n_live: int, reps: int, seed: int, dtype: str, parent_ops: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.paged_attention_q8 import live_order

    mod = _kernel_module(kernel, None)
    key = jax.random.PRNGKey(seed)
    _, case, dims = STATE_KERNELS[kernel]
    state, launch, masked = case(key, dtype, mod, dims)
    layers = state.shape[0]
    active = jnp.zeros((SLOTS,), bool).at[jax.random.permutation(jax.random.fold_in(key, 2), SLOTS)[:n_live]].set(True)
    order, n = live_order(active)

    def stacked(layer_fn):
        def run(state):
            def layer(j, c):
                st, acc = c
                st, o = layer_fn(st, j)
                return st, acc + o

            out_shape = jax.eval_shape(lambda st: layer_fn(st, 0)[1], state)
            return jax.lax.fori_loop(0, layers, layer, (state, jnp.zeros(out_shape.shape, jnp.float32)))

        return jax.jit(run)

    def masked_layer(st, j):
        new, o = masked(jax.lax.dynamic_index_in_dim(st, j, 0, keepdims=False), active)
        return jax.lax.dynamic_update_index_in_dim(st, new, j, 0), o

    # not donated: every repetition starts from the same state (one copy of it more in the time, every form alike)
    t_k, (s_k, o_k) = _timed(stacked(lambda st, j: launch(mod, st, j, order, n)), state, reps=reps)
    t_copy, _ = _timed(jax.jit(lambda s: s + 0), state, reps=reps)
    live = jnp.where(active)[0]
    slot_bytes = state[0, 0].size * jnp.dtype(dtype).itemsize
    least = n_live * 2 * slot_bytes / HBM_BYTES_S
    us = lambda t: round((t - t_copy) / layers * 1e6, 1)  # noqa: E731
    diff = lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))) if x.size else 0.0  # noqa: E731
    out = {
        "probe": "state", "kernel": kernel, "state_dtype": dtype, "live": n_live, "of": SLOTS, "slot_bytes": slot_bytes,
        "kernel_us_a_layer": us(t_k), "copy_of_the_state_us": round(t_copy * 1e6, 1),
        "least_us_a_layer": round(least * 1e6, 1), "kernel_roofline_pct": round(100 * least / ((t_k - t_copy) / layers), 1),
        "dead_slots_bit_for_bit": bool(jnp.array_equal(s_k[:, ~active], state[:, ~active])),
    }
    if parent_ops is None:
        t_m, (s_m, o_m) = _timed(stacked(masked_layer), state, reps=reps)
        out.update(masked_xla_us_a_layer=us(t_m), max_abs_state_diff=diff(s_k[:, live], s_m[:, live]), max_abs_o_diff=diff(o_k[live], o_m[live]))
    else:  # the other checkout's kernel in place of the masked form: timed, and held to bit for bit (all slots, all layers)
        parent = _kernel_module(kernel, parent_ops)
        t_p, (s_p, o_p) = _timed(stacked(lambda st, j: launch(parent, st, j, order, n)), state, reps=reps)
        out.update(parent_kernel_us_a_layer=us(t_p), max_abs_state_diff_to_parent=diff(s_k, s_p), max_abs_o_diff_to_parent=diff(o_k, o_p),
                   equal_to_parent_bit_for_bit=bool(jnp.array_equal(s_k, s_p) and jnp.array_equal(o_k, o_p)))
    return out


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
    p.add_argument("--kernels", default="gdn", help=f"of {','.join(STATE_KERNELS)}")
    p.add_argument("--parent-ops", default=None, help="another checkout's areal_tpu/ops: its kernels in place of the masked XLA form")
    a = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("gdn_probe needs a TPU: a CPU time is no speed")
        return 2
    lives = [int(x) for x in a.live.split(",") if x]
    for kernel in [k for k in a.kernels.split(",") if k]:
        for dtype in a.state_dtypes.split(","):
            rows = []
            for n in lives:
                rows.append(probe_state(kernel, n, a.reps, a.seed, dtype, a.parent_ops))
                print(json.dumps(rows[-1]), flush=True)
            fit = {"probe": "state_fit", "kernel": kernel, "cell": STATE_KERNELS[kernel][0], "state_dtype": dtype}
            for name in ("kernel", "parent_kernel", "masked_xla"):
                if len(rows) > 1 and f"{name}_us_a_layer" in rows[0]:
                    slope, intercept = np.polyfit(lives, [r[f"{name}_us_a_layer"] for r in rows], 1)  # least squares
                    fit[f"{name}_us_a_slot"], fit[f"{name}_us_a_launch"] = round(float(slope), 2), round(float(intercept), 1)
            fit["least_us_a_slot"] = round(2 * rows[0]["slot_bytes"] / HBM_BYTES_S * 1e6, 2)
            print(json.dumps(fit), flush=True)
    for rows in [int(x) for x in a.rows.split(",") if x]:
        print(json.dumps(probe_scan(rows, a.reps, a.seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
