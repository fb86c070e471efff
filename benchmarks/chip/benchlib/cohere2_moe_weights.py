"""Seeded weights of the ``cohere2_moe`` family (command-a-plus-05-2026), made
on the device in one jitted call, in the served type.

As in ``solar_open2_weights.py`` the benchmark makes the weights, not the
program, in the layout the program's forward reads (stacked per kind of
layer: ``swa_moe`` the window layers, ``attention_moe`` the full layers, each
in model order; ``cohere2_moe_reference.layer_params``): every matrix N(0, the
file's assumed ``initializer_range``), the LayerNorm weights 1 + 0.1 N (no
bias anywhere: the family has none). The head is the embedding (tied).

The expert stack holds the configuration's SHARE: ``num_experts`` experts
under a router of the ``assumed`` ``router_experts`` width (no selection bias:
the family's router has none, so there is nothing to settle as
``glm5_weights.settle_bias`` settles one). Where the file assumes an
``expert_own_share`` a < 1, the held experts of a layer share a part as
``glm5_weights.py``'s do (sqrt(1 - a^2) common + a own); the four shared
experts lie side by side in ``ws_gate`` / ``ws_up`` [D, 4 x width] and
``ws_down`` [4 x width, D], each its own draw.

Where the file assumes an ``attn_out_init_scale`` c, the attention's output
projection ``wo`` alone is drawn at c x ``initializer_range`` (the scaled
initialisation of a residual output projection, 1 / sqrt(2 x the published
depth) = 1/8: GPT-2's and Megatron-LM's). The reason is the router's, not
the attention's: over weights drawn all alike a softmax over thousands of
keys is near an average, the average keeps what every token of a SEQUENCE has
in common and loses what is a token's own, and ``W_o W_v`` at N(0, 0.02) hands
that common vector on 3.3 times as large a layer: by the plain reference the
share of a token's normed input that its whole sequence shares read 0.0003 /
0.010 / 0.14 / 0.54 in the four layers, the fourth layer's router then gave
every token of a sequence nearly the same experts, and the 8 held experts'
share of the picks read 0.009 to 0.150 there as the seed's tokens fell (an
even router: 0.0625; a decode step's touched experts 4.8-5.8 of 8, and
``rollout_tok_s`` with them: PERF.md section 4). The family's router has no
selection bias to settle as ``glm5_weights.settle_bias`` settles one, and
offsets folded into ``W_r`` along one sequence's common vector moved no share
of ANOTHER sequence by 0.003: the vector is the sequence's, not the model's.
A trained model's attention is no average; at 1/8 the common share stays
under a hundredth and a token's picks follow the token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib import cohere2_moe_reference, weights


def shapes(cfg: dict) -> dict:
    d = cohere2_moe_reference.dims(cfg)
    D, Fe, E, Ea, Fs = d["D"], d["Fe"], d["E"], d["E_all"], d["shared"] * d["Fe"]
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    layer = {
        "input_norm": (D,),
        "wq": (D, q),
        "wk": (D, kv),
        "wv": (D, kv),
        "wo": (q, D),
        "w_router": (D, Ea),
        "we_gate": (E, D, Fe),
        "we_up": (E, D, Fe),
        "we_down": (E, Fe, D),
        "ws_gate": (D, Fs),
        "ws_up": (D, Fs),
        "ws_down": (Fs, D),
    }
    out: dict = {"embed": (d["V"], D), "final_norm": (D,)}
    for kind in dict.fromkeys(d["kinds"]):
        n = d["kinds"].count(kind)
        out[f"{kind}_moe"] = {name: (n, *s) for name, s in layer.items()}
    return out


def count(cfg: dict, active: bool = False) -> int:
    """Parameters of the configuration as it is held (every expert it holds;
    the tied embedding once), or ``active``: what one token multiplies (its
    ``num_experts_per_tok`` routed experts a layer)."""
    total = 0
    for path, shape in jax.tree.flatten_with_path(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]:
        n = math.prod(shape)
        if active and path[-1].key.startswith("we_"):
            n = n // shape[1] * int(cfg["num_experts_per_tok"])
        total += n
    return total


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    shp = shapes(cfg)
    a = cfg["assumed"]
    std = float(a["initializer_range"])
    own = float(a.get("expert_own_share", 1.0))
    out_scale = float(a.get("attn_out_init_scale", 1.0))

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name.startswith("we_") and own < 1.0:
                k_all, k_own = jax.random.split(k)
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = (std * out_scale if name == "wo" else std) * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
