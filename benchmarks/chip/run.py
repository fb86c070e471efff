#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

Nothing here knows a cell, a configuration, a traffic mix or a per-layer
metric by name: each is a file found through BENCHMARK.json
(``benchlib/spec.py``). A cell's ``kind`` names its runner,
``benchlib/cells/<kind>.py``.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from benchlib import harness, spec  # noqa: E402


def run_cell(bench, workload: str, seed: int, seconds: float, trace: bool, *, t0=None, rehearsal=None, control=False, short=False) -> dict:
    """One run of one cell -> the result object.

    ``rehearsal`` (tests only, never an option of the command) replaces the
    model and the engine sizes by tiny ones (``tmp``: a directory for the
    run's files) and lets the run proceed off a TPU; its result is marked,
    carries every value the run read and its output check, and is not a
    measurement. ``control`` switches
    on the low-precision control of the output check, and ``short`` allows a
    window too short to finish a request (both ``control.py``)."""
    cell = bench.cell(workload)
    kind = bench.cell_kind(cell["params"]["kind"])
    ctx = {
        "bench": bench,
        "cell": cell,
        "seed": int(seed),
        "seconds": float(seconds),
        "trace": bool(trace),
        "t0": _T0 if t0 is None else t0,
        "rehearsal": rehearsal,
        "control": bool(control),
        # tests of one cell run side by side, each in a directory of its own
        "tmp": (rehearsal or {}).get("tmp") or harness.scratch_dir(bench.root, workload),
    }
    out = kind.run(ctx)
    values, facts = out["values"], out["facts"]
    for name, v in sorted(values.items()):
        harness.log(f"measured {name} = {v!r}")
    metrics = {}
    if not trace:
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is None:
                if short or control:  # a control run is not a measurement
                    continue
                raise RuntimeError(f"cell {workload} did not measure {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        facts = {**facts, "values": values}
        for m in cell["per_layer"]:
            lm = bench.layer_metric(m["name"])
            try:
                v = bench.reader(lm["reader"]).read(lm, facts)
            except KeyError as e:  # no peaks for this device: an error on a
                if rehearsal is None:  # chip, a skipped metric in a rehearsal
                    raise
                harness.log(f"per-layer {m['name']}: {e}")
                continue
            if v is None:
                harness.log(f"per-layer {m['name']}: nothing to read, left out")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    res = {
        "correct": bool(out["correct"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": out["device"],
    }
    if trace and out.get("breakdown"):
        res["breakdown"] = out["breakdown"]
    if rehearsal is not None:
        res["rehearsal"] = {"values": values, "check": {k: v for k, v in facts["check"].items() if k != "per_group"}}
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    bench = spec.Bench(ROOT)
    res = run_cell(bench, a.workload, a.seed, a.seconds, bool(a.trace))
    sys.stdout.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)  # daemon threads of the server must not hold the exit
