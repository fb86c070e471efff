#!/usr/bin/env python3
"""The control of the output check, run by hand on the chip, never by the
benchmark's own runs: the cell at its own size with the nearest precision
below the configured one in the program's place. Every seed must come out
``correct: false``.

    python3 benchmarks/chip/control.py --workload <name> --seeds 11,12,13 --seconds 5

Rollout cells switch on the program's own int8 weights and int8 KV
(``ServerConfig.quantization``, ``kv_quantization``); the train cell, whose
engine has no such path, puts the reference's step computed with int8
matmuls, forward and backward, in the trainer's place (no engine runs).
``--sound 1`` reads the program itself on many seeds in one process. One
seed after another; prints one line a seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from benchlib import harness, spec  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--sound", type=int, default=0, help="1: the program itself, not the control (to read sound seeds in one process)")
    a = p.parse_args(argv)
    bench = spec.Bench(bench_run.ROOT)
    wrong = 0
    for seed in [int(s) for s in a.seeds.split(",")]:
        res = bench_run.run_cell(bench, a.workload, seed, a.seconds, False, t0=time.monotonic(), control=not a.sound, short=True)
        harness.log(f"{'sound' if a.sound else 'control'} seed {seed}: correct={res['correct']}")
        print(json.dumps({"workload": a.workload, "seed": seed, "control": not a.sound, "correct": res["correct"]}), flush=True)
        wrong += res["correct"] != bool(a.sound)
    return 1 if wrong else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
