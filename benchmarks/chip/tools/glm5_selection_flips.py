#!/usr/bin/env python3
"""What ONE swapped pick moves in the ``glm_moe_dsa`` cell's output check: the
2,048th selected token exchanged for the best unselected one (the swap a
rounded index score makes), and the 8th chosen expert exchanged for the 9th
(the swap a rounded router score makes, as ``kanana2_routing_flips.py``
measures on its cell), each in EVERY layer and for EVERY token at once, in the
plain reference alone (float32 against float32: nothing but the swap differs).
Also what a program that skipped the selection (attended to every cached
token), or selected the most recent ``index_topk`` tokens, would read.

Run by hand on the chip (PERF.md section 6 has the readings), never by the
benchmark's own runs:

    chiprun -- python3 benchmarks/chip/tools/glm5_selection_flips.py --seeds 11,12 --tokens 8192

The numbers are mean |logprob - the unperturbed reference's| over the tokens
past ``index_topk`` (before it every variant of the selection selects
everything). The cell's limit has to sit above what the first two read
(rounding makes such swaps, a few a thousand picks: the check's floor) and
under what the last two read (a wrong rule: what it must find).
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

from benchlib import glm5_reference, glm5_weights, kanana2_reference, spec, traffic  # noqa: E402

CELL = "rollout-glm-5-ep16-d6-longctx-grpo"


def selections(topk: int):
    """{variant: select(scores, pos, topk)} over the reference's own rule."""
    import jax
    import jax.numpy as jnp

    sound = glm5_reference.select

    def swapped_last(scores, pos, k):  # the k-th best out, the (k + 1)-th in, wherever more than k are cached
        T = scores.shape[1]
        causal = pos[:, None] >= jnp.arange(T)[None, :]
        masked = jnp.where(causal, scores, -jnp.inf)
        best = jax.lax.top_k(masked, min(k + 1, T))[0]
        more = (pos + 1 > k)[:, None]
        kth, nxt = best[:, k - 1 : k], best[:, k : k + 1]
        base = sound(scores, pos, k)
        return jnp.where(more, (base & (masked != kth)) | (masked == nxt), base)

    def everything(scores, pos, k):
        return pos[:, None] >= jnp.arange(scores.shape[1])[None, :]

    def most_recent(scores, pos, k):
        s = jnp.arange(scores.shape[1])[None, :]
        return (pos[:, None] >= s) & (s > pos[:, None] - k)

    return {"sound": sound, "swapped_2048th": swapped_last, "no_selection": everything, "last_2048": most_recent}


def swapped_route(route):
    """The router with every token's LAST chosen expert exchanged for its best unchosen one."""
    import jax.numpy as jnp

    def swapped(u, w_router, bias, *, top_k, norm_topk, scale):
        import jax

        s = jax.nn.sigmoid(u @ w_router.astype(jnp.float32))
        order = jax.lax.top_k(s + bias.astype(jnp.float32), top_k + 1)[1]
        chosen = jnp.concatenate([order[:, : top_k - 1], order[:, top_k:]], axis=1)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk:
            picked = picked / (picked.sum(-1, keepdims=True) + kanana2_reference.NORM_TOPK_EPS)
        picked = picked * scale
        onehot = chosen[:, :, None] == jnp.arange(s.shape[-1])[None, None, :]
        return jnp.sum(jnp.where(onehot, picked[:, :, None], 0.0), axis=1), chosen, jnp.zeros(u.shape[0])

    return swapped


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="11")
    p.add_argument("--tokens", type=int, default=8192)
    a = p.parse_args(argv)
    cfg = spec.Bench().cell(CELL)["model"]
    topk = int(cfg["index_topk"])
    for seed in [int(s) for s in a.seeds.split(",")]:
        params = glm5_weights.make_params(cfg, seed, jnp.bfloat16)
        ids = traffic.rng_for(seed, 21).integers(0, int(cfg["vocab_size"]), a.tokens)
        out = {"seed": seed, "tokens": a.tokens}
        base = None
        for name, rule in selections(topk).items():
            glm5_reference.select = rule
            glm5_reference._index_block.clear_cache()
            lp = glm5_reference.token_logprobs(params, cfg, ids, pad_to=a.tokens)
            if base is None:
                base = lp
            else:
                out[name] = float(np.abs(lp - base)[topk:].mean())
        glm5_reference.select = selections(topk)["sound"]
        glm5_reference._index_block.clear_cache()
        sound_route = kanana2_reference.route
        kanana2_reference.route = swapped_route(sound_route)
        kanana2_reference._expert_ffn.clear_cache()
        try:
            lp = glm5_reference.token_logprobs(params, cfg, ids, pad_to=a.tokens)
        finally:
            kanana2_reference.route = sound_route
            kanana2_reference._expert_ffn.clear_cache()
        out["swapped_8th_expert"] = float(np.abs(lp - base)[topk:].mean())
        out["swapped_8th_expert_all_tokens"] = float(np.abs(lp - base).mean())
        print(json.dumps(out), flush=True)
        del params
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
