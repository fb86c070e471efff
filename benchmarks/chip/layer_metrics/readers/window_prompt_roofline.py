"""The window layers' prompt-pass attention's share of the chip's peak.

A layer that attends to the last ``sliding_window`` tokens has, over a prompt
of n tokens, ``sum_t min(t + 1, sliding_window)`` (query, key) pairs inside
its band: that is the work of its prompt pass whatever implements the
product. A pair costs

    operations: 4 x num_attention_heads x head_dim     (q . k and p v, every query head)

and the least time is the total over the chip's bfloat16 peak (operations
bind: a key's row is read for thousands of queries), over the device seconds
of the ops under ``scopes`` (the window layers' attention) inside the runs of
``within_module`` (the prompt programs). The same work whatever computes it:
a launch that also visits tiles outside the band (its oldest and its diagonal
tile are each half masked), or an XLA form that computes a whole block of
``sliding_window`` x 2 ``sliding_window`` logits, reads LOW, never high. The
pairs come from the program's own counter (``pairs_counter``: counted from
the rows' lengths where the prompt program is dispatched, x window layers),
read at two instants inside the traced span; a prompt pass dispatched before
an edge and run after it is counted on one side and timed on the other, so
with a handful of prompt passes a span the share may read a pass high or low
(the log line says how many prompt programs the trace holds). None where the
program has no such counter or scope, or the configuration no
``sliding_window``."""
from benchlib import harness, peaks

import importlib.util
import os
import re


def pair_ops(cfg: dict) -> int:
    """Operations of one (query, key) pair in one window layer, every query
    head, at the configuration's published sizes."""
    hd = int(cfg.get("head_dim") or cfg["assumed"]["head_dim"])
    return 4 * int(cfg["num_attention_heads"]) * hd


def has_window(cfg: dict) -> bool:
    """Whether the configuration has layers that keep a window (a Qwen2 file
    names a ``sliding_window`` it switches off by ``use_sliding_window``)."""
    return bool(cfg.get("sliding_window")) and bool(cfg.get("use_sliding_window", True))


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or not has_window(facts["cfg"]):
        return None
    pairs = counters.get(metric["pairs_counter"], 0.0)
    if pairs <= 0:
        harness.log(f"{metric['pairs_counter']} did not move in the traced span: a program without the counter, or no prompt pass")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the band's operations")
        return None
    secs = found[1]
    if secs <= 0:
        return None
    rx = re.compile(metric["within_module"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    ops_1 = pair_ops(facts["cfg"])
    least = pairs * ops_1 / peaks.peaks_for(facts["device_kind"])["flops_bf16"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} prompt programs; {pairs:.3e} (query, key, window layer) pairs "
        f"inside the band x {ops_1} operations: least time {least:.4f} s, bound by compute"
    )
    return 100.0 * least / secs
