"""Seeded weights of the ``olmo_hybrid`` family, made on the device in one
jitted call, in the served type.

As in ``weights.py`` the benchmark makes the weights, not the program, in the
layout the program's forward reads (stacked per kind of layer, ``gdn`` and
``attention``): every matrix N(0, the file's assumed ``initializer_range``),
norms 1 + 0.1 N. Three kinds of leaves are drawn as the linear-attention
layer's published initialisation draws them, so that its gates do what they
do in a trained model: the depthwise conv taps uniform in +-1/sqrt(taps) (the
default of the source's ``nn.Conv1d``: at N(0, 0.02) the SiLU behind the conv
would be linear and q, k, v shrink to nothing before the L2 norm);
``A_log = log U(0, 16)`` and ``dt_bias`` = the inverse softplus of
``exp U(log 0.001, log 0.1)``: a head's decay a token is
exp(-A softplus(. + dt_bias)), so some heads forget within a token and some
remember thousands, and the state the chip holds is one that thousands of
decode steps accumulate into. In the Olmo block every sublayer's output goes
through an RMSNorm before it joins the residual stream, so each mixer moves
the hidden state by its full share whatever the matrices' scale: a wrong
state, window or gate moves the logprobs.
"""

from __future__ import annotations

import math

from benchlib import olmo_hybrid_reference, weights


def shapes(cfg: dict) -> dict:
    d = olmo_hybrid_reference.dims(cfg)
    D, F, H, K, V, taps = d["D"], d["F"], d["gh"], d["gk"], d["gv"], d["taps"]
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    block = {"input_norm": (D,), "post_norm": (D,), "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    mixers = {
        "gdn": {
            "q_proj": (D, H * K), "k_proj": (D, H * K), "v_proj": (D, H * V), "a_proj": (D, H), "b_proj": (D, H),
            "g_proj": (D, H * V), "q_conv_w": (taps, 1, H * K), "k_conv_w": (taps, 1, H * K), "v_conv_w": (taps, 1, H * V),
            "A_log": (H,), "dt_bias": (H,), "o_norm": (V,), "o_proj": (H * V, D),
        },
        "attention": {"wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D), "q_norm": (q,), "k_norm": (kv,)},
    }
    out: dict = {"embed": (d["V"], D), "final_norm": (D,), "lm_head": (d["V"], D)}
    for kind in dict.fromkeys(d["kinds"]):
        n = d["kinds"].count(kind)
        out[kind] = {name: (n, *s) for name, s in {**block, **mixers[kind]}.items()}
    return out


def count(cfg: dict) -> int:
    """Parameters of the configuration as it is run."""
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shp = shapes(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    taps = int(cfg["linear_conv_kernel_dim"])

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("conv_w"):
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(taps)
            elif name == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
