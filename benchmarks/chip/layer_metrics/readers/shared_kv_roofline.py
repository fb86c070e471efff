"""The shared K/V cache read's share of its roofline in a decode step.

In a decoder-hybrid-decoder ONE full-attention layer keeps every token's keys
and values, and that layer and every cross-attention layer past it read them:
a decode step has to read, once a reading layer, the K and V rows of every
cached token of every LIVE slot. That is the least it can do, whatever
implements the product. A (cached token, reading layer) costs

    bytes:      2 x num_key_value_heads x head_dim x 2     (K and V: 5,120 at 20 heads of 64, bfloat16)
    operations: 2 x num_attention_heads x head_dim         (scores: every query head against its key)
              + 2 x num_attention_heads x 2 head_dim       (values: every query head over its pair's [v1 | v2])

and the least time is max(bytes / peak bytes/s, operations / peak
operations/s) of their totals (bytes bind: 5,120 B at 819 GB/s is 6.3 ns,
15,360 operations at 197 TFLOP/s 0.08 ns), over the device seconds of the ops
under ``scopes`` (the full layer's read and the cross layers') inside the
runs of ``within_module``. A page fetched but masked, a query padded to the
pair's width or operands in a wider type read LOW, never high. The (token,
layer) reads come from the program's own counter (``tokens_counter``: cached
tokens of live slots x the layers that read them, counted on the device
inside the chunk program), read beside the chunk counter at two instants
inside the traced span: their ratio, reads a chunk, times the chunk programs
the trace holds, counted as their device seconds over a whole run's (the
median run's), as ``latent_cache_roofline`` reckons. None where the program
has no such counter or scope, or the configuration no window beside a full
layer."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics


def token_layer_cost(cfg: dict, bytes_per: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one cached token for one layer that reads the
    shared pages in a decode step, at the configuration's published sizes."""
    hd = int(cfg.get("head_dim") or cfg["assumed"]["head_dim"])
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return 2 * heads * hd + 2 * heads * 2 * hd, 2 * kv_heads * hd * bytes_per


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or facts["cfg"].get("model_type") != "phi4flash":
        return None
    reads, chunks = counters.get(metric["tokens_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if reads <= 0 or chunks <= 0:
        harness.log(f"{metric['tokens_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the shared rows' bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    ops_1, bytes_1 = token_layer_cost(facts["cfg"])
    n = reads / chunks * n_calls
    r = peaks.roofline(n * ops_1, n * bytes_1, secs, peaks.peaks_for(facts["device_kind"]))
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); "
        f"{reads / chunks:.0f} (cached token, reading layer) reads a chunk ({reads:.0f} over {chunks:.0f} chunks) x {bytes_1} B and {ops_1} operations: "
        f"least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
