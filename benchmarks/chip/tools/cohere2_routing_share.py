#!/usr/bin/env python3
"""How far the held experts' share of a token's picks follows the weights'
seed in the ``cohere2_moe`` cell: the family's router has NO selection bias
(nothing to settle as ``glm5_weights.settle_bias`` settles one), so which of
the 128 experts a token picks is what a random ``W_r`` makes of the token's
normed input, and of what its whole sequence has in common there.

Run by hand on the chip (PERF.md section 4 has the readings), never by the
benchmark's own runs:

    chiprun -- python3 benchmarks/chip/tools/cohere2_routing_share.py --seeds 11,12,13,14,15,16 --tokens 4096

For each seed: the cell's weights, the plain reference's forward over one
sequence of seeded tokens, and per layer the share of the tokens' 8 picks that
fall on the 8 held experts (1/16 under an even router) and how many of the 8
a decode step of 22 live rows would touch (what ``rollout_tok_s`` follows:
ISSUE 51 reckons 6.1), beside the share of a token's normed input that every
token has in common. ``--out-scale 1`` reads weights drawn all alike, ``--out-scale c``
the attention's output projection at c x the range, without the flag as the
configuration file says. One JSON
line a seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.dirname(os.path.dirname(CHIP)))

from benchlib import cohere2_moe_reference as ref  # noqa: E402
from benchlib import cohere2_moe_weights, spec, traffic  # noqa: E402

CELL = "rollout-command-a-plus-ep16-d4-longctx-grpo"


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="11")
    p.add_argument("--tokens", type=int, default=4096)
    p.add_argument("--rows", type=int, default=22, help="live rows of the decode step whose touched experts are reckoned")
    p.add_argument("--out-scale", type=float, default=-1.0, help="attn_out_init_scale in the configuration's place (1: every matrix at initializer_range; -1: the file's)")
    a = p.parse_args(argv)
    cell = spec.Bench().cell(CELL)
    cfg = dict(cell["model"])
    if a.out_scale >= 0:
        cfg["assumed"] = {**cfg["assumed"], "attn_out_init_scale": a.out_scale}
    d = ref.dims(cfg)
    for seed in [int(s) for s in a.seeds.split(",")]:
        params = cohere2_moe_weights.make_params(cfg, seed, jnp.dtype(cell["params"]["dtype"]))
        ids = jnp.asarray(traffic.rng_for(seed, 5).integers(0, d["V"], a.tokens), jnp.int32)
        shares, touched, common = [], [], []
        with jax.default_matmul_precision("highest"):
            x = params["embed"][ids].astype(ref.F32)
            for i in range(d["layers"]):
                lp = ref.layer_params(params, cfg, i)
                u = ref.layernorm(x, lp["input_norm"], d["eps"])
                win = d["window"] if d["kinds"][i] == "swa" else 0
                att = ref.attention(u, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], window=win, theta=d["theta"])
                m, s = ref.moe(u, lp, top_k=d["K"], norm_topk=d["norm_topk"], e0=d["e0"], n_shared=d["shared"])
                x = x + att + m
                mean_u = jnp.mean(u, axis=0)  # the share of a token's normed input that every token has: |mean|^2 / mean |u|^2
                common.append(float(jnp.sum(mean_u * mean_u) / jnp.mean(jnp.sum(u * u, axis=-1))))
                picks = np.asarray(jax.lax.top_k(s, d["K"])[1])  # [T, K] global ids
                held = (picks >= d["e0"]) & (picks < d["e0"] + d["E"])
                shares.append(float(held.mean()))
                per_expert = np.asarray([(picks == d["e0"] + e).any(axis=1).mean() for e in range(d["E"])])  # P(a row picks e)
                touched.append(float((1.0 - (1.0 - per_expert) ** a.rows).sum()))
        print(json.dumps({"seed": seed, "tokens": a.tokens, "held_share_by_layer": [round(v, 5) for v in shares], "even": d["E"] / d["E_all"], "attn_out_init_scale": float(cfg["assumed"].get("attn_out_init_scale", 1.0)), "common_share_of_u_by_layer": [round(v, 4) for v in common],
                          "touched_of_held_at_rows": a.rows, "touched_by_layer": [round(v, 3) for v in touched], "touched_mean": round(float(np.mean(touched)), 3)}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
