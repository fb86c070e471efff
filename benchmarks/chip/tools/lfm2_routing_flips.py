#!/usr/bin/env python3
"""How often the served program and the plain reference pick other experts,
and what the output check reads on the tokens whose choice was not close.

Run by hand on the chip (PERF.md section 4 has the readings), never by the
benchmark's own runs:

    chiprun -- python3 benchmarks/chip/tools/lfm2_routing_flips.py --seeds 11,12 --tokens 3072 --variants sound,int8,no_bias

A router is a discrete choice: where a token's 4th and 5th biased scores are
nearly tied, the program's bfloat16 hidden state and the reference's float32
one fall on different sides, and that token's logprob then moves by far more
than rounding. This counts it: the cell's configuration and seeded weights,
a sequence of seeded tokens, the program's own prefill forward in the served
type against ``lfm2_reference``, which routes for itself. The program's
picks are read by a spy on ``moe.expert_ffn`` (a host callback a layer: this
tool's, the program's forward returns no such thing). Prints, a seed, the
share of (token, expert layer) pairs whose SETS of experts differ, by layer,
and the mean |logprob - reference| over the tokens whose smallest margin
(the reference's own 4th biased score less its 5th, over the expert layers)
is at least each of ``--margins`` (PR 30 found that a large margin in the
reference does not keep a token from a swap: the layers below have moved its
hidden state by then). ``--variants``: ``sound`` is the program as served;
``int8`` rounds its FFN weights as the cell's control does; ``no_bias`` is a
program that forgot the selection bias (a routing fault the check has to
find); ``--own`` overrides the configuration's ``expert_own_share``.
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

from benchlib import lfm2_reference, lfm2_weights, spec, traffic  # noqa: E402

CELL = "rollout-lfm2-8b-a1b-d14-grpo"


def program_forward(mcfg):
    """A function (params, ids [T]) -> (log p(ids[t+1] | ids[:t+1]) [T-1], the
    experts the program picked [expert layers, T, K]) of the program's own
    prefill forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import hybrid, moe

    picked: list = []
    inner = moe.expert_ffn

    def spy(x, layer, cfg, **kw):
        out = inner(x, layer, cfg, **kw)
        jax.debug.callback(lambda e: picked.append(np.asarray(e)), out[2], ordered=True)
        return out

    @jax.jit
    def fwd(params, ids):
        moe.expert_ffn = spy
        try:
            hidden = hybrid.forward_prefill(params, mcfg, ids[None], jnp.ones_like(ids)[None])[0]
        finally:
            moe.expert_ffn = inner
        lp = jax.nn.log_softmax(hybrid.compute_logits(params, mcfg, hidden)[0].astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp[:-1], ids[1:, None], axis=-1)[:, 0]

    def run(params, ids):
        del picked[:]
        lp = np.asarray(fwd(params, jnp.asarray(ids)))
        jax.effects_barrier()
        return lp, np.stack(picked)

    return run


def main(argv=None) -> int:
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="11")
    p.add_argument("--tokens", type=int, default=1024)
    p.add_argument("--variants", default="sound", help="comma list of sound, int8, no_bias")
    p.add_argument("--margins", default="0,0.005")
    p.add_argument("--own", type=float, default=None, help="expert_own_share in the configuration's place")
    p.add_argument("--tiny", type=int, default=0, help="1: a toy size, to rehearse off the chip")
    a = p.parse_args(argv)
    bench = spec.Bench()
    cell = bench.cell(CELL)
    cfg, fam = dict(cell["model"]), cell["params"]["family"]
    dtype = cell["params"]["dtype"]
    if a.tiny:
        cfg.update(vocab_size=512, hidden_size=64, intermediate_size=96, moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=2, num_experts=8)
        cfg["assumed"] = {**cfg["assumed"], "head_dim": 16}
    if a.own is not None:
        cfg["assumed"] = {**cfg["assumed"], "expert_own_share": a.own}
    kind = bench.cell_kind("rollout_family")
    mcfg = kind.model_config(cfg, fam, dtype)
    run = program_forward(mcfg)
    for seed in [int(s) for s in a.seeds.split(",")]:
        ids = traffic.rng_for(seed, 13).integers(0, int(cfg["vocab_size"]), a.tokens).astype(np.int32)
        params = lfm2_weights.make_params(cfg, seed, mcfg.jax_dtype)
        ref = lfm2_reference.token_logprobs(params, cfg, ids, pad_to=a.tokens)
        want, margin = lfm2_reference.routing_of(params, cfg, ids, pad_to=a.tokens)
        want, least = np.sort(want, axis=-1), margin[:, :-1].min(axis=0)  # the predicting positions: [T-1]
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
            err = np.abs(lp.astype(np.float64) - ref.astype(np.float64))
            own = sets_differ[:, :-1].any(0)  # the predicting position picked other experts in some layer
            by_margin = {}
            for m in [float(x) for x in a.margins.split(",")]:
                keep = least >= m
                by_margin[str(m)] = {
                    "tokens_share": float(keep.mean()),
                    "mean_abs": float(err[keep].mean()) if keep.any() else None,
                    "median_abs": float(np.median(err[keep])) if keep.any() else None,
                    "flipped_share": float(own[keep].mean()) if keep.any() else None,
                }
            print(json.dumps({
                "seed": seed, "tokens": int(a.tokens), "dtype": dtype, "variant": variant, "expert_own_share": cfg["assumed"].get("expert_own_share"),
                "pairs_differ_share": float(sets_differ.mean()),
                "by_layer": [round(float(x), 5) for x in sets_differ.mean(axis=1)],
                "tokens_with_no_flip_share": float(1 - own.mean()),
                "mean_abs": float(err.mean()),
                "mean_abs_no_own_flip": float(err[~own].mean()) if (~own).any() else None,
                "margin_quartiles_by_layer": [[round(float(q), 5) for q in np.percentile(row, [25, 50, 75])] for row in margin],
                "by_min_margin": by_margin,
            }), flush=True)
        del params
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
