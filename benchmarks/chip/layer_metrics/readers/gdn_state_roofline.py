"""The delta-rule state update's share of its memory roofline in a decode
step.

A decode step of a model with gated-delta-rule layers has to read and write
the matrix state of every LIVE slot once a layer: that is the least it can
do, whatever it does with the slots that hold no request. The least time is

    (slot, layer) updates in the traced span x 2 x state_bytes(cfg) / peak bytes/s

over the device seconds of the ops under ``scopes`` (the recurrence's scope)
inside the runs of ``within_module``. The updates come from the program's own
counter (``updates_counter``: counted on the device for live slots only,
where the kernel walks them), read beside the chunk counter at two instants
inside the traced span: their ratio, updates a chunk, times the chunk
programs the trace holds (the counters are credited when a chunk drains, the
trace holds the chunks that ran: the ratio carries over, the instants need
not match). The trace cuts the first and the last chunk program it sees: the
programs are counted as their device seconds over a whole run's (the median
run's), not by number (``moe_weight_roofline`` reckons so too). Bound: memory
(some ten operations a byte). The scope also holds the gated norm on the
recurrence's output, which moves no state: the share reads lower for it, not
higher. None where the program has no such counter or scope."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def state_bytes(cfg: dict) -> int:
    """Bytes of one slot's delta-rule state in one layer, at the
    configuration's published sizes and in the type its file states:
    [linear_num_value_heads, linear_key_head_dim, linear_value_head_dim]."""
    n = int(cfg["linear_num_value_heads"]) * int(cfg["linear_key_head_dim"]) * int(cfg["linear_value_head_dim"])
    return n * _DTYPE_BYTES[cfg.get("assumed", {}).get("gdn_state_dtype", "float32")]


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or "linear_value_head_dim" not in facts["cfg"]:
        return None
    updates, chunks = counters.get(metric["updates_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if updates <= 0 or chunks <= 0:
        harness.log(f"{metric['updates_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the state's bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    per_update = 2 * state_bytes(facts["cfg"])
    least = updates / chunks * n_calls * per_update / peaks.peaks_for(facts["device_kind"])["hbm_bytes_s"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); "
        f"{updates / chunks:.1f} (slot, layer) updates a chunk ({updates:.0f} over {chunks:.0f} chunks) x {per_update / 1e6:.2f} MB "
        f"read and written: least time {least:.4f} s, bound by memory"
    )
    return 100.0 * least / secs
