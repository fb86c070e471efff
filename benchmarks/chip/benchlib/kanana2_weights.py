"""Seeded weights of the ``deepseek_v3`` family (kanana-2-30b-a3b), made on
the device in one jitted call, in the served type.

As in ``weights.py`` the benchmark makes the weights, not the program, in the
layout the program's forward reads (stacked per kind of layer: ``mla`` the
leading dense layers, ``mla_moe`` the expert layers,
``kanana2_reference.layer_params``): every matrix N(0, the file's assumed
``initializer_range``), norms 1 + 0.1 N (``kv_norm``, the latent's, too).
``router_bias`` (the checkpoint's ``e_score_correction_bias``, a buffer that
the published training moves outside the gradient) is N(0, the same range):
against sigmoid scores that spread over 0.3-0.7 it changes some selections
and leaves most, so that a program that took its gates from the biased scores
would be found.

The expert stack holds the configuration's SHARE: ``n_routed_experts`` experts
(those a chip of the stated deployment holds), under a router and a bias of
the ``assumed`` ``router_experts`` width. Where the file assumes an
``expert_own_share`` a < 1, the held experts of a layer share a part as
``lfm2_weights.py``'s do (sqrt(1 - a^2) common + a own); without the key
every expert is its own.
"""

from __future__ import annotations

import math

from benchlib import kanana2_reference, weights


def shapes(cfg: dict) -> dict:
    d = kanana2_reference.dims(cfg)
    D, F, Fe, Fs, E, Ea, H = d["D"], d["F"], d["Fe"], d["Fs"], d["E"], d["E_all"], d["heads"]
    layer = {
        "input_norm": (D,),
        "post_norm": (D,),
        "wq": (D, H * (d["nope"] + d["rope"])),
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
                shared = jax.random.normal(k_all, (shape[0], 1, *shape[2:]), dtype)
                x = std * (math.sqrt(1.0 - own * own) * shared + own * jax.random.normal(k_own, shape, dtype))
            else:
                x = std * jax.random.normal(k, shape, dtype)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))
