"""Seeded weights of the ``solar_open2`` family (Solar-Open2-250B), made on the
device in one jitted call, in the served type.

As in ``glm5_weights.py`` the benchmark makes the weights, not the program, in
the layout the program's forward reads (stacked per kind of layer:
``attention_moe`` the gated attention layers, ``kda_moe`` the delta-rule
layers, each in model order; ``solar_open2_reference.layer_params``): every
matrix N(0, the file's assumed ``initializer_range``), norms 1 + 0.1 N. Three
kinds of leaves of a ``kda`` layer are drawn as Kimi Linear's published
initialisation (Mamba-2's) draws them, so that its gates do what they do in a
trained model: the depthwise conv taps uniform in +-1/sqrt(taps) (at N(0,
0.02) the SiLU behind the conv would be linear and q, k, v shrink to nothing
before the L2 norm); ``A_log = log U(1, 16)`` a head and ``dt_bias`` = the
inverse softplus of ``exp U(log 0.001, log 0.1)`` a head AND key channel: a
channel's log decay a token is -A softplus(. + dt_bias), between -0.001 and
-1.6, so some channels forget within a token and some remember thousands,
and the state the chip holds is one that thousands of decode steps accumulate
into (a chunk of 64 tokens at -1.6 a token is the -100 that ``k exp(G)``
against ``k exp(-G)`` would overflow on).

The expert stack holds the configuration's SHARE: ``n_routed_experts`` experts
under a router and a bias of the ``assumed`` ``router_experts`` width. Where
the file assumes an ``expert_own_share`` a < 1, the held experts of a layer
share a part as ``glm5_weights.py``'s do (sqrt(1 - a^2) common + a own), and
where it assumes ``router_balance_tokens`` n, ``router_bias`` is what the
rule that trains it leaves on n seeded tokens, layer after layer
(``glm5_weights.settle_bias``; the reasons are at that module's head and in
PERF.md section 4).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib import solar_open2_reference, weights
from benchlib.glm5_weights import balance_tokens, settle_bias


def shapes(cfg: dict) -> dict:
    d = solar_open2_reference.dims(cfg)
    D, Fe, Fs, E, Ea = d["D"], d["Fe"], d["Fs"], d["E"], d["E_all"]
    H, K, taps = d["lh"], d["lk"], d["taps"]
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    block = {
        "input_norm": (D,),
        "post_norm": (D,),
        "w_router": (D, Ea),
        "router_bias": (Ea,),
        "we_gate": (E, D, Fe),
        "we_up": (E, D, Fe),
        "we_down": (E, Fe, D),
        "ws_gate": (D, Fs),
        "ws_up": (D, Fs),
        "ws_down": (Fs, D),
    }
    mixers = {
        "attention": {"wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D), "wg": (D, q)},
        "kda": {
            "q_proj": (D, H * K), "k_proj": (D, H * K), "v_proj": (D, H * K), "f_a": (D, K), "f_b": (K, H * K),
            "b_proj": (D, H), "g_a": (D, K), "g_b": (K, H * K), "q_conv_w": (taps, 1, H * K), "k_conv_w": (taps, 1, H * K),
            "v_conv_w": (taps, 1, H * K), "A_log": (H,), "dt_bias": (H * K,), "o_norm": (K,), "o_proj": (H * K, D),
        },
    }
    out: dict = {"embed": (d["V"], D), "final_norm": (D,), "lm_head": (d["V"], D)}
    for kind in dict.fromkeys(d["kinds"]):
        n = d["kinds"].count(kind)
        out[f"{kind}_moe"] = {name: (n, *s) for name, s in {**block, **mixers[kind]}.items()}
    return out


def count(cfg: dict, active: bool = False) -> int:
    """Parameters of the configuration as it is held (every expert it holds),
    or ``active``: what one token multiplies (its ``num_experts_per_tok``
    routed experts a layer)."""
    total = 0
    for path, shape in jax.tree.flatten_with_path(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]:
        n = math.prod(shape)
        if active and path[-1].key.startswith("we_"):
            n = n // shape[1] * int(cfg["num_experts_per_tok"])
        total += n
    return total


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``;
    then, where the file asks for it, the router's bias settled."""
    shp = shapes(cfg)
    a = cfg["assumed"]
    std = float(a["initializer_range"])
    own = float(a.get("expert_own_share", 1.0))
    taps = int(cfg["linear_attn_config"]["short_conv_kernel_size"])

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
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif name.startswith("we_") and own < 1.0:
                k_all, k_own = jax.random.split(k)
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    params = jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
    n = int(a.get("router_balance_tokens", 0))
    if n:
        bias = balanced_router_bias(params, cfg, seed, n)
        for stack, rows in bias.items():
            old = params[stack]["router_bias"]
            params[stack]["router_bias"] = jax.device_put(rows, old.sharding)
    return params


def balanced_router_bias(params: dict, cfg: dict, seed: int, n_tokens: int) -> dict:
    """{stack: ``router_bias`` [its layers, E_all] in the weights' type}: each
    layer's bias settled (``glm5_weights.settle_bias``) on the router scores
    of one sequence of ``n_tokens`` seeded tokens, computed by the reference
    with the layers before it routed under THEIR settled bias."""
    d = solar_open2_reference.dims(cfg)
    ids = balance_tokens(cfg, seed, n_tokens)
    settled: list = []

    def rebias(scores):
        dtype = params[f"{d['kinds'][len(settled)]}_moe"]["router_bias"].dtype
        settled.append(settle_bias(scores, d["K"]).astype(dtype))
        return settled[-1]

    with jax.default_matmul_precision("highest"):
        solar_open2_reference.hidden_states(params, cfg, ids, rebias=rebias)
    return {
        f"{kind}_moe": jnp.stack([b for b, at in zip(settled, d["kinds"]) if at == kind]) for kind in dict.fromkeys(d["kinds"])
    }
