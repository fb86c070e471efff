"""Seeded weights of the ``glm_moe_dsa`` family (GLM-5), made on the device in
one jitted call, in the served type.

As in ``kanana2_weights.py`` the benchmark makes the weights, not the program,
in the layout the program's forward reads (stacked per kind of layer: ``mla``
the leading dense layers, ``mla_moe`` the expert layers,
``glm5_reference.layer_params``): every matrix N(0, the file's assumed
``initializer_range``), norms 1 + 0.1 N (``kv_norm``, ``q_a_norm`` and the index
key's ``wi_k_norm`` too), the index key's norm bias 0.1 N, ``router_bias`` (the
checkpoint's ``e_score_correction_bias``) N(0, the same range).

One matrix is drawn wider, ``w_qb`` by the assumed ``attn_query_gain``: with
every matrix at 0.02 a head's logits over the cached tokens have a standard
deviation of 0.8 (0.905 x 0.4525 x sqrt(192) over the latent part, 0.905 x
1.568 x sqrt(64) over the rotary key, over sqrt(256)) and the softmax over ten
thousand of them is nearly flat: a program that skipped the selection, or
selected by another rule, would move its outputs less than rounding does. At a
gain of 4 the deviation is 3.2 and a query's mass sits on a few tokens, as a
trained model's does: drop one of them from the selected set and the output
moves. The index's own matrices stay at 0.02: its scores spread over tens of
their rounding either way (a query . key over 128 values of deviation 0.9 and
1), and only their ORDER enters the result.

The expert stack holds the configuration's SHARE: ``n_routed_experts`` experts
under a router and a bias of the ``assumed`` ``router_experts`` width. Where
the file assumes an ``expert_own_share`` a < 1, the held experts of a layer
share a part as ``lfm2_weights.py``'s do (sqrt(1 - a^2) common + a own): a
router is a discrete choice, rounding swaps a token's 8th expert for its 9th
in a share of the tokens whatever the weights' scale, and between independent
experts one swap moves a logprob by more than int8 weights do (PERF.md
section 4: 0.32 sound against 0.38 for the control with independent experts).

Where the file assumes ``router_balance_tokens`` n, ``router_bias`` is not left
random: it is what the rule that trains it (``topk_method`` ``noaux_tc``,
DeepSeek-V3's balancing without an auxiliary loss: after a batch an
overloaded expert's bias goes down a step and an underloaded one's up) leaves
behind on n seeded tokens, layer after layer, each layer routed under its
settled bias before the next one is read (``balanced_router_bias``). A
checkpoint's experts are loaded alike by construction. Seeded matrices are
not: the normed residual stream has a part every token shares, a router row's
product with it is an offset on that expert's every score, and the busiest of
256 experts drew 2.5-3.2 x the mean load in every layer and seed, so that the
16 held here drew 0.43-0.61 of a token's 8 a layer and a decode step touched
7.2-9.4 of them, as the seed fell: `rollout_tok_s` followed the seed by 0.86%
over six seeds and by 1.4-1.9% between the quartiles of the driver's twelve
(PERF.md section 6).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchlib import glm5_reference, weights


def shapes(cfg: dict) -> dict:
    d = glm5_reference.dims(cfg)
    D, F, Fe, Fs, E, Ea, H = d["D"], d["F"], d["Fe"], d["Fs"], d["E"], d["E_all"], d["heads"]
    layer = {
        "input_norm": (D,),
        "post_norm": (D,),
        "w_qa": (D, d["q_rank"]),
        "q_a_norm": (d["q_rank"],),
        "w_qb": (d["q_rank"], H * (d["nope"] + d["rope"])),
        "wi_qb": (d["q_rank"], d["i_heads"] * d["i_dim"]),
        "wi_k": (D, d["i_dim"]),
        "wi_k_norm": (d["i_dim"],),
        "wi_k_norm_bias": (d["i_dim"],),
        "wi_w": (D, d["i_heads"]),
        "w_kva": (D, d["rank"] + d["rope"]),
        "kv_norm": (d["rank"],),
        "w_kvb": (d["rank"], H * (d["nope"] + d["vd"])),
        "wo": (H * d["vd"], D),
    }
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
    for stack, n in (("mla", d["dense"]), ("mla_moe", d["layers"] - d["dense"])):
        if n:
            out[stack] = {name: (n, *s) for name, s in {**layer, **ffns[stack]}.items()}
    return out


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``;
    then, where the file asks for it, the router's bias settled."""
    shp = shapes(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    gain = float(cfg["assumed"].get("attn_query_gain", 1.0))
    own = float(cfg["assumed"].get("expert_own_share", 1.0))

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("norm_bias"):
                x = 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.startswith("we_") and own < 1.0:
                k_all, k_own = jax.random.split(k)
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = (std * gain if name == "w_qb" else std) * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    params = jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
    n = int(cfg["assumed"].get("router_balance_tokens", 0))
    if n and "mla_moe" in params:
        old = params["mla_moe"]["router_bias"]
        params["mla_moe"]["router_bias"] = jax.device_put(balanced_router_bias(params, cfg, seed, n), old.sharding)
    return params


@functools.partial(jax.jit, static_argnames=("top_k", "steps"))
def settle_bias(scores, top_k: int, steps: int = 400, first: float = 0.1, last: float = 1e-4):
    """The selection bias [E] under which the top ``top_k`` of ``scores``
    [T, E] + bias load every expert alike: ``steps`` rounds of the training
    rule (bias += rate x sign(mean load - load)), the rate falling from
    ``first`` to ``last`` (sigmoid scores lie in (0, 1): the bias can cross
    them, and settles to a ten-thousandth). Only differences between biases
    choose, so the mean is taken out."""
    T, E = scores.shape

    def step(i, bias):
        picks = jax.lax.top_k(scores + bias, top_k)[1]
        load = jnp.zeros(E, jnp.float32).at[picks.reshape(-1)].add(1.0)
        rate = first * (last / first) ** (i / (steps - 1))
        return bias + rate * jnp.sign(T * top_k / E - load)

    bias = jax.lax.fori_loop(0, steps, step, jnp.zeros(E, jnp.float32))
    return bias - bias.mean()


def balance_tokens(cfg: dict, seed: int, n_tokens: int):
    """The seeded sequence a seed's router bias is settled on."""
    return jax.random.randint(jax.random.fold_in(weights.seed_key(seed), 11), (int(n_tokens),), 0, int(cfg["vocab_size"]))


def balanced_router_bias(params: dict, cfg: dict, seed: int, n_tokens: int):
    """``router_bias`` [expert layers, E_all] in the weights' type: each
    layer's bias settled (``settle_bias``) on the router scores of one
    sequence of ``n_tokens`` seeded tokens, computed by the reference with the
    layers before it routed under THEIR settled bias."""
    d = glm5_reference.dims(cfg)
    dtype = params["mla_moe"]["router_bias"].dtype
    ids = balance_tokens(cfg, seed, n_tokens)
    settled = []

    def rebias(scores):
        settled.append(settle_bias(scores, d["K"]).astype(dtype))
        return settled[-1]

    with jax.default_matmul_precision("highest"):
        glm5_reference.hidden_states(params, cfg, ids, rebias=rebias)
    return jnp.stack(settled)
