"""Seeded weights of the ``sdar_moe`` family, made on the device in one
jitted call, in the served type.

As in ``lfm2_weights.py`` the benchmark makes the weights, not the program, in
the layout the program's forward reads (``models/qwen.py``: one stack a leaf
over the layers, ``embed``, ``final_norm`` and an untied ``lm_head``): every
matrix N(0, the file's assumed ``initializer_range``), norms (the per-head
ones of q and k too) 1 + 0.1 N. The experts of a layer share a part: each of
their three matrices is sqrt(1 - a^2) times one matrix common to the layer's
experts plus a = ``expert_own_share`` (the file's ``assumed``) times a matrix
of the expert's own, both N(0, ``initializer_range``), so every weight has the
std it would have alone. The model has no shared expert, so whatever every
token needs of its FFN every routed expert must hold; and the output check
needs it: a bfloat16 program and a float32 reference pick another 8th expert
wherever the 8th and 9th of 128 softmax scores are nearly tied, which on any
weights whose router logits are near Gaussian is some percent of tokens a
layer, and with independent experts one such swap moves a logprob by several
times what the arithmetic's rounding does. At a = 0.25 a swap moves it by a
quarter of that and the check reads the arithmetic; a program that picked
WRONG experts throughout would still read several times the limit.
"""

from __future__ import annotations

import math

from benchlib import weights


def shapes(cfg: dict) -> dict:
    D, E, Fe = int(cfg["hidden_size"]), int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    hd, L, V = int(cfg["head_dim"]), int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    q, kv = int(cfg["num_attention_heads"]) * hd, int(cfg["num_key_value_heads"]) * hd
    layer = {
        "wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D), "input_norm": (D,), "post_attn_norm": (D,),
        "w_router": (D, E), "we_gate": (E, D, Fe), "we_up": (E, D, Fe), "we_down": (E, Fe, D),
    }
    if cfg["assumed"]["qk_norm"]:
        layer.update(q_norm=(hd,), k_norm=(hd,))
    return {
        "embed": (V, D),
        "layers": {name: (L, *s) for name, s in layer.items()},
        "final_norm": (D,),
        "lm_head": (V, D),
    }


def param_count(cfg: dict) -> int:
    flat = shapes(cfg)
    return sum(math.prod(s) for s in (*flat["layers"].values(), flat["embed"], flat["final_norm"], flat["lm_head"]))


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shp = shapes(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    own = float(cfg["assumed"].get("expert_own_share", 1.0))

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
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)  # one a layer, for its experts
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
