#!/usr/bin/env python3
"""What one span of the program costs with no profiler session running, run by
hand on the chip's host (a host number: the chip does nothing here).

    chiprun -- python3 benchmarks/chip/tools/span_cost.py [--other <checkout>]

Prints ns a ``trace_scope`` (its TraceMe, and since PR 34 one entry of the span
record), the same with ``cpu=True`` (a pass, a train step) where the program
has it, and ns an ``instant``; the least of five rounds of 300,000, as a
request for jax's profiler module has been made (a process without jax
annotates nothing). ``--other`` names a second checkout, such as a ``git
archive`` of the parent, whose ``perf_tracer`` is timed first in the same
process. PERF.md Findings (PR 34) holds the readings.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
N, ROUNDS = 300_000, 5


def load(root: str, name: str):
    """``areal_tpu/utils/perf_tracer.py`` of the checkout at ``root``, as a
    module of its own (its imports resolve in this checkout's package)."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(root, "areal_tpu", "utils", "perf_tracer.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def ns_a_call(body) -> float:
    best = float("inf")
    for _ in range(ROUNDS):
        t = time.perf_counter_ns()
        body(N)
        best = min(best, (time.perf_counter_ns() - t) / N)
    return best


def time_module(mod, label: str) -> None:
    def spans(n, **kw):
        for _ in range(n):
            with mod.trace_scope("areal.decode.dispatch", **kw):
                pass

    def instants(n):
        for _ in range(n):
            mod.instant("areal.request.admitted")

    print(f"{label}: span {ns_a_call(spans):.0f} ns", end="")
    if "cpu" in inspect.signature(mod.trace_scope).parameters:
        print(f", span with cpu_us {ns_a_call(lambda n: spans(n, cpu=True)):.0f} ns", end="")
    print(f", instant {ns_a_call(instants):.0f} ns")
    take = getattr(mod.get_tracer(), "record", None)
    if take is not None:
        print(f"{label}: the record holds {len(take().entries)} entries after {(2 * ROUNDS + ROUNDS) * N} spans and events (bounded)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--other", help="a second checkout to time first")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax.profiler  # noqa: F401  a span annotates only where jax's profiler is imported

    print(f"python {sys.version.split()[0]}, jax {jax.__version__}, {os.cpu_count()} cores, no profiler session")
    if args.other:
        time_module(load(args.other, "perf_tracer_other"), f"other ({args.other})")
    time_module(load(ROOT, "perf_tracer_here"), "this checkout")


if __name__ == "__main__":
    main()
