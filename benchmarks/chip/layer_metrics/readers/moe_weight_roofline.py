"""The expert matmuls' share of their memory roofline in a decode step.

A decode step of a model with sparse experts has to read the three matrices
of every expert that at least one LIVE slot's row chose, once a layer: that
is the least it can do, however it dispatches, and whatever it does with
experts nobody chose or with slots that hold no request. The least time is

    experts touched in the traced span x expert_bytes(cfg) / peak bytes/s

over the device seconds of the ops under ``scopes`` (the expert matmuls'
scope) inside the runs of ``within_module``. Experts touched come from the
program's own counter (``touched_counter``: per step and layer, experts with
at least one live row), read beside the chunk counter at two instants inside
the traced span: their ratio, touched a chunk, times the chunk programs the
trace holds (the counters are credited when a chunk drains, the trace holds
the chunks that ran: the ratio carries over, the instants need not match).
The trace cuts the first and the last chunk program it sees: the programs
are counted as their device seconds over a whole run's (the median run's),
not by number, or the bytes of two cut runs would be set against the seconds
of their traced part only and the share would read up to a tenth high.
Bound: memory (a few rows an expert: under 20 operations a byte). None where
the program has no such counter or scope."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics


def expert_bytes(cfg: dict, bytes_per: int = 2) -> int:
    """Bytes of one expert's three matrices (gate, up, down) at the
    configuration's published widths."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"]) * bytes_per


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or "moe_intermediate_size" not in facts["cfg"]:
        return None
    touched, chunks = counters.get(metric["touched_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if touched <= 0 or chunks <= 0:
        harness.log(f"{metric['touched_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the experts' bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    per_expert = expert_bytes(facts["cfg"])
    least = touched / chunks * n_calls * per_expert / peaks.peaks_for(facts["device_kind"])["hbm_bytes_s"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); {touched / chunks:.1f} (layer, expert) reads a chunk "
        f"({touched:.0f} over {chunks:.0f} chunks) x {per_expert / 1e6:.2f} MB an expert: least time {least:.4f} s, bound by memory"
    )
    return 100.0 * least / secs
