"""Seeded weights of the ``phi4flash`` family (a decoder-hybrid-decoder),
made on the device in one jitted call, in the served type.

As in ``weights.py`` the benchmark makes the weights, not the program, in the
layout the program's forward reads (stacked per kind of layer: ``s6``,
``swa``, ``attention``, ``gmu``, ``cross``): every matrix and projection bias
N(0, the file's assumed ``initializer_range``); LayerNorm weights 1 + 0.1 N
and biases 0.02 N, the norm over a pair's values 1 + 0.1 N, so that a forward
that dropped one would not agree with the reference. The selective scan's
leaves are drawn as Mamba's published initialisation draws them, so that the
state is one that thousands of decode steps accumulate into and not one that
forgets within a token: ``A_log = log(1..state size)`` in every channel (the
S4D-real rule), ``D`` = 1, ``dt_bias`` the inverse softplus of ``exp U(log
0.001, log 0.1)``, ``dt_proj`` uniform in +-rank^-1/2, the depthwise conv's
taps and bias uniform in +-1/sqrt(taps) (the default of the source's
``nn.Conv1d``). Lambda's four vectors a layer are N(0, 0.1), as the
Differential Transformer draws them: lam then stands near l0.
"""

from __future__ import annotations

import math

from benchlib import phi4flash_reference, weights


def shapes(cfg: dict) -> dict:
    d = phi4flash_reference.dims(cfg)
    D, F, di, N, R = d["D"], d["F"], d["inner"], d["N"], d["rank"]
    q, kv, hd = d["heads"] * d["hd"], d["kv_heads"] * d["hd"], d["hd"]
    block = {
        "input_norm": (D,), "input_norm_bias": (D,), "post_norm": (D,), "post_norm_bias": (D,),
        "w_gate_up": (D, 2 * F), "w_down": (F, D),
    }
    q_side = {"wq": (D, q), "wo": (q, D), "lq1": (hd,), "lk1": (hd,), "lq2": (hd,), "lk2": (hd,), "sub_norm": (2 * hd,)}
    kv_side = {"wk": (D, kv), "wv": (D, kv)}
    if d["bias"]:
        q_side.update(wq_b=(q,), wo_b=(D,))
        kv_side.update(wk_b=(kv,), wv_b=(kv,))
    mixers = {
        "s6": {
            "in_proj": (D, 2 * di), "conv_w": (d["taps"], 1, di), "conv_b": (di,), "x_proj": (di, R + 2 * N),
            "dt_proj": (R, di), "dt_bias": (di,), "A_log": (N, di), "D": (di,), "out_proj": (di, D),
        },
        "swa": {**q_side, **kv_side},
        "attention": {**q_side, **kv_side},
        "cross": dict(q_side),
        "gmu": {"gmu_in": (D, di), "gmu_out": (di, D)},
    }
    out: dict = {"embed": (d["V"], D), "final_norm": (D,), "final_norm_bias": (D,)}
    for kind in dict.fromkeys(d["kinds"]):
        n = d["kinds"].count(kind)
        out[kind] = {name: (n, *s) for name, s in {**block, **mixers[kind]}.items()}
    return out


def count(cfg: dict) -> int:
    """Parameters of the configuration as it is run (the head is the embedding)."""
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shp = shapes(cfg)
    d = phi4flash_reference.dims(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    taps, rank = d["taps"], d["rank"]

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("norm_bias"):
                x = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name in ("conv_w", "conv_b"):
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(taps)
            elif name == "A_log":  # [layers, state size, channels]: log(1..N) down the state index
                x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape)
            elif name == "D":
                x = jnp.ones(shape, jnp.float32)
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "dt_proj":
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(rank)
            elif name in ("lq1", "lk1", "lq2", "lk2"):
                x = 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
