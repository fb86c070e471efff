#!/usr/bin/env python3
"""What the delta-rule state's precision does to the two checks of
``rollout-olmo-hybrid-7b-d16-grpo``, run by hand on the chip.

    chiprun -- python3 benchmarks/chip/tools/olmo_state_precision.py --seeds 11,12 --dtypes bfloat16,float32

For each seed and each type of the state (the configuration's
``assumed.gdn_state_dtype`` replaced, nothing else: sound weights, bfloat16
pages) it runs the cell's own kind at the cell's own size under its own
traffic for ``--seconds`` and prints one line: (i) the output check's mean
|logprob - reference| beside ``check.limit`` and (ii) ``state_rel`` of the
first linear-attention layer against the reference's token-by-token state on
the probe's 8 requests of 256 + 768 tokens beside ``check.limit_state_rel``.
PERF.md section 4 holds the readings: which of the two limits a bfloat16
state fails decides whether the cell needs the state probe at all.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from benchlib import harness, spec  # noqa: E402

CELL = "rollout-olmo-hybrid-7b-d16-grpo"


def one(bench, seed: int, dtype: str, seconds: float) -> dict:
    cell = copy.deepcopy(bench.cell(CELL))
    cell["model"]["assumed"]["gdn_state_dtype"] = dtype
    ctx = {
        "bench": bench, "cell": cell, "seed": seed, "seconds": seconds, "trace": False, "t0": time.monotonic(),
        "rehearsal": None, "control": False, "tmp": harness.scratch_dir(bench.root, CELL),
    }
    out = bench.cell_kind("rollout_family_probe").run(ctx)
    chk, lim = out["facts"]["check"], cell["params"]["check"]
    return {
        "seed": seed, "gdn_state_dtype": dtype, "correct": out["correct"], "failed": out["failed"],
        "mean_abs": chk["mean_abs"], "limit": lim["limit"], "state_rel": chk.get("state_rel"), "limit_state_rel": lim["limit_state_rel"],
        "state_rel_head_mean": chk.get("state_rel_head_mean"), "rollout_tok_s": out["values"].get("rollout_tok_s"),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--dtypes", default="bfloat16,float32")
    p.add_argument("--seconds", type=float, default=10.0)
    a = p.parse_args(argv)
    bench = spec.Bench(bench_run.ROOT)
    for seed in [int(s) for s in a.seeds.split(",")]:
        for dtype in a.dtypes.split(","):
            print(json.dumps(one(bench, seed, dtype, a.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
