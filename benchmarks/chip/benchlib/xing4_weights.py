"""Seeded weights of the ``xing4_0`` family (Xing4.0-29B-A4B), made on the
device in one jitted call, in the served type.

As in ``glm5_weights.py`` the benchmark makes the weights, not the program, in
the layout the program's forward reads (stacked per kind of layer: ``mla`` the
leading dense layers, ``mla_moe`` the expert layers,
``xing4_reference.layer_params``): every matrix N(0, the file's assumed
``initializer_range``), norms 1 + 0.1 N, ``router_bias`` (the checkpoint's
``e_score_correction_bias``) N(0, the same range) or, where the file assumes
``router_balance_tokens``, what its training rule leaves on that many seeded
tokens (``glm5_weights.settle_bias``, layer after layer under the settled
biases before it). Where the file assumes an ``expert_own_share`` a < 1 the
held experts of a layer share a part (sqrt(1 - a^2) common + a own), as
``glm5_weights.py`` says why.

The stream coefficients are NOT drawn as the hyper-connections papers start
them (gains ``a_*`` near 0: there ``Phi`` does nothing, every token has the
same coefficients, and a program with a wrong ``Phi`` would pass the check).
``hc_seeded``: ``a_pre = a_post = a_res = 1``; ``Phi`` N(0, 1 / (n D)), so that
``m = x' Phi`` has unit variance over tokens (``x'`` has unit mean square);
``b_pre``, ``b_post`` N(0, 1); ``B_res = 2 I + N(0, 0.5)``: a token's
coefficients then move by tens of percent with its streams, ``H_res`` is dense
with a heavier diagonal, and the Sinkhorn rounds have work to do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib import glm5_weights, weights, xing4_reference


def shapes(cfg: dict) -> dict:
    d = xing4_reference.dims(cfg)
    D, F, Fe, Fs, E, Ea, H, n = d["D"], d["F"], d["Fe"], d["Fs"], d["E"], d["E_all"], d["heads"], d["n"]
    n_coeff = n * (2 + n)
    layer = {
        "input_norm": (D,),
        "post_norm": (D,),
        "w_qa": (D, d["q_rank"]),
        "q_a_norm": (d["q_rank"],),
        "w_qb": (d["q_rank"], H * (d["nope"] + d["rope"])),
        "w_kva": (D, d["rank"] + d["rope"]),
        "kv_norm": (d["rank"],),
        "w_kvb": (d["rank"], H * (d["nope"] + d["vd"])),
        "wo": (H * d["vd"], D),
    }
    for tag in ("attn", "ffn"):
        layer.update({f"hc_{tag}_phi": (n * D, n_coeff), f"hc_{tag}_alpha": (3,), f"hc_{tag}_bias": (n_coeff,)})
    ffns = {
        "mla": {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)},
        "mla_moe": {
            "w_router": (D, Ea),
            "router_bias": (Ea,),
            "we_gate": (E, D, Fe),
            "we_up": (E, D, Fe),
            "we_down": (E, Fe, D),
            "ws_gate": (D, Fs),
            "ws_up": (D, Fs),
            "ws_down": (Fs, D),
        },
    }
    out: dict = {"embed": (d["V"], D), "lm_head": (d["V"], D), "final_norm": (D,)}
    for stack, count in (("mla", d["dense"]), ("mla_moe", d["layers"] - d["dense"])):
        if count:
            out[stack] = {name: (count, *s) for name, s in {**layer, **ffns[stack]}.items()}
    return out


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``;
    then, where the file asks for it, the router's bias settled."""
    shp = shapes(cfg)
    d = xing4_reference.dims(cfg)
    n = d["n"]
    std = float(cfg["assumed"]["initializer_range"])
    own = float(cfg["assumed"].get("expert_own_share", 1.0))
    res_diag = jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])  # [b_pre | b_post | B_res]: 2 I under the last
    res_std = jnp.concatenate([jnp.ones(2 * n), jnp.full((n * n,), 0.5)])

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("_alpha"):
                x = jnp.ones(shape, jnp.float32)
            elif name.startswith("hc_") and name.endswith("_bias"):
                x = res_diag + res_std * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("_phi"):
                x = jax.random.normal(k, shape, dtype) / math.sqrt(shape[-2])
            elif name.startswith("we_") and own < 1.0:
                k_all, k_own = jax.random.split(k)
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    params = jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
    tokens = int(cfg["assumed"].get("router_balance_tokens", 0))
    if tokens and "mla_moe" in params:
        old = params["mla_moe"]["router_bias"]
        params["mla_moe"]["router_bias"] = jax.device_put(balanced_router_bias(params, cfg, seed, tokens), old.sharding)
    return params


def balanced_router_bias(params: dict, cfg: dict, seed: int, n_tokens: int):
    """``router_bias`` [expert layers, E_all] in the weights' type: each
    layer's bias settled (``glm5_weights.settle_bias``) on the router scores
    of one sequence of ``n_tokens`` seeded tokens, computed by the reference
    with the layers before it routed under THEIR settled bias."""
    d = xing4_reference.dims(cfg)
    dtype = params["mla_moe"]["router_bias"].dtype
    ids = glm5_weights.balance_tokens(cfg, seed, n_tokens)
    settled = []

    def rebias(scores):
        settled.append(glm5_weights.settle_bias(scores, d["K"]).astype(dtype))
        return settled[-1]

    with jax.default_matmul_precision("highest"):
        xing4_reference.streams_after(params, cfg, ids, rebias=rebias)
    return jnp.stack(settled)
