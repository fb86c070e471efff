#!/usr/bin/env python3
"""Whether the output check of ``rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo``
SEES the mechanisms the family adds: the cell is run as ``run.py`` runs it
(the served system, the traffic, the window, the sample of finished
requests), and the server's logprobs of those requests are then set against
the plain reference computed five times over: as the configuration states, and
with ONE mechanism left out of the REFERENCE each time,

  res_identity  ``H_res`` the identity in every sublayer (the gain ``a_res`` 0,
                ``B_res`` +30 on the diagonal and -30 off it: the clamp's edges)
  one_round     ONE Sinkhorn round for the configuration's 20
  no_yarn       the rotary pairs at plain ``rope_theta`` frequencies, the
                softmax scale without YaRN's factor
  coeff_bf16    the coefficients (``x' Phi``, the sigmoids, exp, the rounds)
                computed in bfloat16 (the ``assumed`` ``hc_coeff_dtype``)

A check whose limit passes a reference without a mechanism does not see that
mechanism. Run by hand on the chip (PERF.md section 4 has the readings), never
by the benchmark's own runs:

    chiprun --timeout 2400 -- python3 benchmarks/chip/tools/xing4_mechanisms.py --seed 11 --seconds 30

One JSON line: {variant: mean |logprob - reference|} over the checked tokens,
beside the cell's limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)

import run as bench_run  # noqa: E402
from benchlib import harness, spec, xing4_reference  # noqa: E402

CELL = "rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo"


def res_identity(params: dict, n: int) -> dict:
    """``params`` with every sublayer's ``H_res`` pinned to the identity."""
    import jax.numpy as jnp

    out = dict(params)
    edge = jnp.where(jnp.eye(n, dtype=bool), 30.0, -30.0).reshape(-1)
    for stack in ("mla", "mla_moe"):
        if stack not in params:
            continue
        leaves = dict(params[stack])
        for tag in ("attn", "ffn"):
            alpha, bias = leaves[f"hc_{tag}_alpha"], leaves[f"hc_{tag}_bias"]
            leaves[f"hc_{tag}_alpha"] = alpha.at[:, 2].set(0.0)
            leaves[f"hc_{tag}_bias"] = bias.at[:, 2 * n :].set(jnp.broadcast_to(edge, (bias.shape[0], n * n)).astype(bias.dtype))
        out[stack] = leaves
    return out


def variants(params: dict, cfg: dict) -> dict:
    """{name: (params, cfg)} of the reference without each mechanism."""
    assumed = cfg.get("assumed", {})
    return {
        "res_identity": (res_identity(params, int(cfg["hc_mult"])), cfg),
        "one_round": (params, {**cfg, "hc_sinkhorn_iters": 1}),
        "no_yarn": (params, {k: v for k, v in cfg.items() if k != "rope_scaling"}),
        "coeff_bf16": (params, {**cfg, "assumed": {**assumed, "hc_coeff_dtype": "bfloat16"}}),
    }


def main(argv=None) -> int:
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--only", default="", help="comma-separated variants (default: all four)")
    a = p.parse_args(argv)
    bench = spec.Bench(bench_run.ROOT)
    sound = xing4_reference.token_logprobs
    seen: list[tuple[list[int], dict]] = []

    def recording(params, cfg, ids, pad_to):
        ref = sound(params, cfg, ids, pad_to)
        others = {
            name: sound(v_params, v_cfg, ids, pad_to)
            for name, (v_params, v_cfg) in variants(params, cfg).items()
            if not a.only or name in a.only.split(",")
        }
        seen.append((list(map(int, ids)), {"sound": ref, **others}))
        return ref

    xing4_reference.token_logprobs = recording  # the cell's kind finds the reference by name: this module
    res = bench_run.run_cell(bench, CELL, a.seed, a.seconds, False, t0=time.monotonic())
    with open(os.path.join(bench.root, ".bench_tmp", CELL, "records.json")) as f:
        records = json.load(f)["records"]
    prompts = {(r["client"], r["group"]): r["prompt"] for r in records if "prompt" in r}
    got = {tuple(prompts[(r["client"], r["group"])] + r["tokens"]): r["logprobs"] for r in records if r.get("ok") and (r["client"], r["group"]) in prompts}
    errs: dict[str, list] = {}
    for ids, refs in seen:
        served = np.asarray(got[tuple(ids)], np.float64)
        for name, ref in refs.items():
            errs.setdefault(name, []).append(np.abs(served - ref[len(ids) - 1 - len(served) :].astype(np.float64)))
    out = {name: float(np.concatenate(e).mean()) for name, e in errs.items()}
    limit = float(bench.cell(CELL)["params"]["check"]["limit"])
    harness.log(f"seed {a.seed}: {len(seen)} requests; mean |logprob - reference| by the reference's variant: {out}; limit {limit}")
    print(json.dumps({"seed": a.seed, "correct": res["correct"], "limit": limit, "mean_abs": out}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
