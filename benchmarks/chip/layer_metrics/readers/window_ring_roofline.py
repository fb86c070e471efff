"""The window layers' ring read's share of its memory roofline in a decode step.

A layer that attends to the last ``sliding_window`` tokens keeps them in a
ring of the slot's own, and a decode step has to read, once a window layer,
the K and V rows of min(cached tokens, ``sliding_window``) tokens of every
LIVE slot: that is the least it can do, whatever implements the read and
whatever it does with the slots that hold no request. A (ring token, window
layer) costs

    bytes: 2 x num_key_value_heads x head_dim x 2     (K and V: 4,096 at 8 heads of 128, bfloat16)

and the least time is the total over the chip's bytes a second (bytes bind:
16 query heads a KV head are 64 operations a byte, under the chip's 240),
over the device seconds of the ops under ``scopes`` (the window layers' read
and the step's ring bookkeeping) inside the runs of ``within_module``. A page
fetched whole for a ring that is not full, or a ring's table made again a
layer, reads LOW, never high. The (token, layer) reads come from the program's
own counter (``tokens_counter``: ring tokens of live slots x window layers,
counted on the device inside the chunk program), read beside the chunk counter
at two instants inside the traced span: their ratio, reads a chunk, times the
chunk programs the trace holds, counted as their device seconds over a whole
run's (the median run's), as ``shared_kv_roofline`` beside this file reckons.
None where the program has no such counter or scope, or the configuration no
``sliding_window``."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics


def ring_token_bytes(cfg: dict, bytes_per: int = 2) -> int:
    """Bytes of one ring token's K and V rows in one window layer, at the
    configuration's published sizes."""
    hd = int(cfg.get("head_dim") or cfg["assumed"]["head_dim"])
    return 2 * int(cfg["num_key_value_heads"]) * hd * bytes_per


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
    reads, chunks = counters.get(metric["tokens_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if reads <= 0 or chunks <= 0:
        harness.log(f"{metric['tokens_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the rings' bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    per_token = ring_token_bytes(facts["cfg"])
    least = reads / chunks * n_calls * per_token / peaks.peaks_for(facts["device_kind"])["hbm_bytes_s"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); "
        f"{reads / chunks:.0f} (ring token, window layer) reads a chunk ({reads:.0f} over {chunks:.0f} chunks) x {per_token} B: "
        f"least time {least:.4f} s, bound by memory"
    )
    return 100.0 * least / secs
