"""Seeded weights of the ``lfm2_moe`` family, made on the device in one
jitted call, in the served type.

As in ``weights.py`` the benchmark makes the weights, not the program, in the
layout the program's forward reads (stacked per kind of layer: a layer's
mixer and its FFN, ``lfm2_reference.stack_of``): every matrix N(0, the file's
assumed ``initializer_range``), norms 1 + 0.1 N. Three kinds of leaves are
drawn otherwise. ``router_bias`` (the checkpoint's ``expert_bias``, a buffer that
the published training moves outside the gradient) is N(0, the same range):
against sigmoid scores that spread over 0.3-0.7 it changes some selections
and leaves most, so that a program that took its gates from the biased
scores would be found. The depthwise conv taps are uniform in
+-1/sqrt(conv_L_cache), the default of the source's ``nn.Conv1d``: at
N(0, 0.02) the conv mixer's output would vanish beside the FFN's and a wrong
window in a slot's state would move no logprob. The experts of a layer share
a part: each of their three matrices is sqrt(1 - a^2) times one matrix common
to the layer's experts plus a = ``expert_own_share`` (the file's ``assumed``)
times a matrix of the expert's own, both N(0, ``initializer_range``), so every
weight has the std it would have alone. The model has no shared expert, so
whatever every token needs of its FFN every routed expert must hold; and the
output check needs it: a bfloat16 program and a float32 reference pick
another 4th expert wherever the 4th and 5th biased scores are nearly tied
(4% of tokens in the first expert layer on ANY weights whose router logits
are near Gaussian, more below it, PERF.md section 4), and with independent
experts that one swap moves a logprob by 0.1, five times what the
arithmetic's rounding does. At a = 0.25 a swap moves it by a quarter of that
and the check reads the arithmetic; a program that picked WRONG experts
throughout would still read several times the limit.
"""

from __future__ import annotations

import math

from benchlib import lfm2_reference, weights


def shapes(cfg: dict) -> dict:
    d = lfm2_reference.dims(cfg)
    D, F, Fe, E = d["D"], d["F"], d["Fe"], d["E"]
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    norms = {"input_norm": (D,), "post_norm": (D,)}
    mixers = {
        "conv": {"in_proj": (D, 3 * D), "conv_w": (d["taps"], 1, D), "out_proj": (D, D)},
        "attention": {"wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D), "q_norm": (d["hd"],), "k_norm": (d["hd"],)},
    }
    ffns = {
        "dense": {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)},
        "moe": {"w_router": (D, E), "router_bias": (E,), "we_gate": (E, D, Fe), "we_up": (E, D, Fe), "we_down": (E, Fe, D)},
    }
    out: dict = {"embed": (d["V"], D), "final_norm": (D,)}
    count: dict[str, int] = {}
    for kind, ffn in zip(d["kinds"], d["ffns"]):
        stack = lfm2_reference.stack_of(kind, ffn)
        count[stack] = count.get(stack, 0) + 1
        out[stack] = {**norms, **ffns[ffn], **mixers[kind]}
    for stack, n in count.items():
        out[stack] = {name: (n, *s) for name, s in out[stack].items()}
    return out


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, in one program, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shp = shapes(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    own = float(cfg["assumed"].get("expert_own_share", 1.0))
    taps = int(cfg["conv_L_cache"])

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if name.endswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name == "conv_w":
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(taps)
            elif name.startswith("we_") and own < 1.0:
                k_all, k_own = jax.random.split(k)
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
