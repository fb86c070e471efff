"""Seeded weights of the hybrid family (``granitemoehybrid`` without
experts), made on the device in one jitted call, in the served type.

As in ``weights.py`` the benchmark makes the weights, not the program, in the
layout the program's forward reads (stacked per layer kind): every matrix
N(0, the file's assumed ``initializer_range``), norms 1 + 0.1 N, the conv bias random too. Four
leaves are drawn as the published Mamba-2 initialisation draws them, because
at N(0, 0.02) every head would forget within a few tokens and the recurrent
part of the mixer's output would vanish beside its skip term, so that neither
the state's precision nor its bookkeeping would be tested: ``A`` uniform in
1-16 (``A_log`` its log), ``dt`` log-uniform in 0.001-0.1 (``dt_bias`` its
inverse softplus), ``D`` = 1, and the depthwise conv weight uniform in
+-1/sqrt(d_conv) (the default of the source's ``nn.Conv1d``). Some heads then
remember thousands of tokens.
"""

from __future__ import annotations

import math

from benchlib import hybrid_reference, weights


def shapes(cfg: dict) -> dict:
    d = hybrid_reference.dims(cfg)
    D, F, H, C = d["D"], d["F"], d["H"], d["conv_dim"]
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    shared = {"input_norm": (D,), "post_norm": (D,), "w_gate_up": (D, 2 * F), "w_down": (F, D)}
    mamba = {
        **shared,
        "in_proj": (D, 2 * d["d_inner"] + 2 * d["G"] * d["N"] + H),
        "conv_w": (d["K"], 1, C),
        "conv_b": (C,),
        "dt_bias": (H,),
        "A_log": (H,),
        "D": (H,),
        "ssm_norm": (d["d_inner"],),
        "out_proj": (d["d_inner"], D),
    }
    attention = {**shared, "wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D)}
    out = {"embed": (d["V"], D), "final_norm": (D,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d["V"], D)
    for kind, n, leaves in (("mamba", d["n_mamba"], mamba), ("attention", d["n_attention"], attention)):
        if n:
            out[kind] = {name: (n, *s) for name, s in leaves.items()}
    return out


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shp = shapes(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    k_conv = int(cfg["mamba_d_conv"])

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "D":
                x = jnp.ones(shape, jnp.float32)
            elif name == "conv_w":
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(k_conv)
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
