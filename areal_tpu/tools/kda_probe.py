"""Time the ``kda`` prompt scan of one layer alone on the chip at the published
sizes of ``rollout-solar-open2-ep16-d8-longctx-grpo`` (64 heads of 128 x 128,
a block of 1,024 tokens as ``hybrid.kda_prefill`` walks a prompt).

    chiprun -- python -m areal_tpu.tools.kda_probe [--heads 1,2,4,8] [--tokens 1024]

The XLA form (``hybrid.kda_chunked_scan``) against the launch
(``ops/kda_prompt_scan.py``) by heads a grid step, in microseconds a block
and a chunk of 64 tokens, against the products' own time on the MXU (float32
at ``highest``: six bfloat16 passes), with the largest difference between the
two on the final state and on ``o`` (the compiled kernel's own check:
interpret mode on the CPU cannot see what the chip's compiler does), once at
the configuration's decays and once with log decays that pass -320 a chunk.
TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

from areal_tpu.tools.gdn_probe import _timed

H, K, V = 64, 128, 128
MXU_FLOPS = 197e12  # TPU v5e, bfloat16, as benchmarks/chip/benchlib/peaks.py


def _inputs(key, L: int, strong: bool):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (L, H, K))) * K**-0.5
    k = unit(jax.random.normal(ks[1], (L, H, K)))
    v = jax.random.normal(ks[2], (L, H, V))
    a = -jnp.exp(jax.random.uniform(ks[3], (L, H, K), minval=jnp.log(1e-3), maxval=jnp.log(1.6)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (L, H)))
    if strong:
        a = a.at[:, :, :4].set(-5.0)
    s0 = 0.1 * jax.random.normal(ks[5], (H, K, V))
    return q, k, v, a, beta, s0


def chunk_flops(C: int = 64, sub: int = 16) -> float:
    """Float32 multiply-adds x 2 of one head's chunk as the launch makes them
    (the earlier sub-blocks, two doublings of the inverse, the writes, the
    reads of the state, the state's update), each six bfloat16 passes."""
    nb = C // sub
    mm = lambda m, k, n: 2.0 * m * k * n  # noqa: E731
    f = (nb - 1) * mm(2 * sub, K, C) + 2 * 2 * mm(C, C, C) + mm(C, C, V) + mm(C, C, K)
    f += mm(2 * C, K, V) + mm(C, C, V) + mm(K, C, V)
    return 6.0 * f


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--heads", default="1,2,4,8")
    p.add_argument("--tokens", type=int, default=1024)
    p.add_argument("--reps", type=int, default=10)
    a = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU: a CPU time is no speed"}))
        return 1
    from areal_tpu.models import hybrid
    from areal_tpu.ops.kda_prompt_scan import CHUNK, kda_prompt_scan

    L = a.tokens
    nc = -(-L // CHUNK)
    n = jnp.asarray(L - 37, jnp.int32)
    out = {"device": jax.devices()[0].device_kind, "tokens": L, "heads": H, "mxu_us_a_chunk": chunk_flops() * H / MXU_FLOPS * 1e6, "rows": []}
    xla = jax.jit(hybrid.kda_chunked_scan)
    for strong in (False, True):
        args = _inputs(jax.random.PRNGKey(int(strong)), L, strong)
        q, k, v, al, beta, s0 = args
        t, (s_x, o_x) = _timed(xla, q, k, v, al, beta, n, s0, reps=a.reps)
        row = {"decays": "-320 a chunk" if strong else "published", "xla_us": t * 1e6, "xla_us_a_chunk": t * 1e6 / nc, "launch": {}}
        for hb in (int(x) for x in a.heads.split(",")):
            fn = functools.partial(kda_prompt_scan, heads_per_step=hb)  # jitted itself, the heads a static argument
            t0 = time.perf_counter()
            t, (s_k, o_k) = _timed(fn, q, k, v, al, beta, n, s0, reps=a.reps)
            row["launch"][hb] = {
                "us": t * 1e6,
                "us_a_chunk": t * 1e6 / nc,
                "first_call_and_reps_s": time.perf_counter() - t0,
                "state_diff": float(jnp.max(jnp.abs(s_k - s_x))),
                "o_diff": float(jnp.max(jnp.abs(o_k - o_x)[: L - 37])),
                "finite": bool(np.isfinite(np.asarray(o_k)).all() and np.isfinite(np.asarray(s_k)).all()),
            }
        out["rows"].append(row)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
