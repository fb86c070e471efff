#!/usr/bin/env python3
"""``lfm2_routing_flips.py``'s measurement on the ``deepseek_v3`` cell: how
often the served program and the plain reference pick other experts, and
what the output check reads then.

Run by hand on the chip (PERF.md section 4 has the readings), never by the
benchmark's own runs:

    chiprun -- python3 benchmarks/chip/tools/kanana2_routing_flips.py --seeds 11,12 --tokens 3072 --variants sound,no_bias,int8

This model differs from cell 5's in three ways that bear on one swapped
expert: a shared block computes every token's common part whatever the
router does; a token's 6 experts are picked among 128 of which 16 are here,
so most swaps exchange two experts that are both absent and change nothing
but the gates' common denominator; and a swap that does involve a held
expert moves one part in six of a routed sum that is itself one part beside
the shared block. So besides the share of (token, expert layer) pairs whose
SETS of experts differ, this prints the share whose HELD experts differ, and
the mean |logprob - reference| with and without those tokens. The program's
picks are read by the sibling tool's spy on ``moe.expert_ffn``. ``--variants``
as there: ``sound``, ``no_bias`` (a program that forgot the selection bias),
``int8`` (the cell's control, in place: give it last); ``--own`` sets an
``expert_own_share`` the configuration does not have.
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

from benchlib import kanana2_reference, kanana2_weights, spec, traffic  # noqa: E402
from lfm2_routing_flips import program_forward  # noqa: E402  (the sibling tool: the spy and the program's forward)

CELL = "rollout-kanana-2-30b-a3b-ep8-grpo"


def main(argv=None) -> int:
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="11")
    p.add_argument("--tokens", type=int, default=1024)
    p.add_argument("--variants", default="sound", help="comma list of sound, no_bias, int8")
    p.add_argument("--own", type=float, default=None, help="an expert_own_share the configuration does not have")
    p.add_argument("--tiny", type=int, default=0, help="1: a toy size, to rehearse off the chip")
    a = p.parse_args(argv)
    bench = spec.Bench()
    cell = bench.cell(CELL)
    cfg, fam = dict(cell["model"]), cell["params"]["family"]
    dtype = cell["params"]["dtype"]
    if a.tiny:
        cfg.update(
            vocab_size=500, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=4,
            num_hidden_layers=4, kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3,
        )
        cfg["assumed"] = {**cfg["assumed"], "router_experts": 8, "latent_row_lanes": 256}
    if a.own is not None:
        cfg["assumed"] = {**cfg["assumed"], "expert_own_share": a.own}
    kind = bench.cell_kind("rollout_family")
    mcfg = kind.model_config({**cfg, "assumed": {k: v for k, v in cfg["assumed"].items() if k != "expert_own_share"}}, fam, dtype)
    run = program_forward(mcfg)
    d = kanana2_reference.dims(cfg)
    for seed in [int(s) for s in a.seeds.split(",")]:
        ids = traffic.rng_for(seed, 13).integers(0, int(cfg["vocab_size"]), a.tokens).astype(np.int32)
        params = kanana2_weights.make_params(cfg, seed, mcfg.jax_dtype)
        ref = kanana2_reference.token_logprobs(params, cfg, ids, pad_to=a.tokens)
        want, margin = kanana2_reference.routing_of(params, cfg, ids, pad_to=a.tokens)
        want = np.sort(want, axis=-1)
        held = lambda e: np.where((e >= d["e0"]) & (e < d["e0"] + d["E"]), e, -1)  # noqa: E731 — the picks that are here, the others alike
        for variant in a.variants.split(","):
            served = params
            if variant == "int8":  # in place (two copies of the weights do not fit the chip): give it last
                assert variant == a.variants.split(",")[-1]
                served = params = kind.round_int8(params, fam["control"]["round_int8"])
            elif variant == "no_bias":
                served = {k: {**v, "router_bias": 0 * v["router_bias"]} if isinstance(v, dict) and "router_bias" in v else v for k, v in params.items()}
            lp, got = run(served, ids)
            del served
            got = np.sort(got.reshape(want.shape), axis=-1)
            sets_differ = (got != want).any(-1)  # [layers, T]
            held_differ = (np.sort(held(got), -1) != np.sort(held(want), -1)).any(-1)
            err = np.abs(lp.astype(np.float64) - ref.astype(np.float64))
            any_flip, own = sets_differ[:, :-1].any(0), held_differ[:, :-1].any(0)  # of the predicting positions
            print(json.dumps({
                "seed": seed, "tokens": int(a.tokens), "dtype": dtype, "variant": variant, "expert_own_share": cfg["assumed"].get("expert_own_share"),
                "pairs_differ_share": float(sets_differ.mean()),
                "pairs_held_differ_share": float(held_differ.mean()),
                "by_layer_first_mid_last": [round(float(x), 5) for x in sets_differ.mean(axis=1)[[0, len(sets_differ) // 2, -1]]],
                "tokens_with_no_flip_share": float(1 - any_flip.mean()),
                "tokens_with_no_held_flip_share": float(1 - own.mean()),
                "mean_abs": float(err.mean()),
                "mean_abs_no_flip": float(err[~any_flip].mean()) if (~any_flip).any() else None,
                "mean_abs_no_held_flip": float(err[~own].mean()) if (~own).any() else None,
                "mean_abs_held_flip": float(err[own].mean()) if own.any() else None,
                "margin_median_first_mid_last": [round(float(np.median(row)), 5) for row in margin[[0, len(margin) // 2, -1]]],
            }), flush=True)
        del params
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
