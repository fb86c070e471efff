"""A learned index's two reads in a decode step, each as a share of its
roofline (``"what"`` in the metric file says which).

Where a latent-attention layer has an index that picks the ``index_topk``
cached tokens a query attends to, a decode step has to do two things once a
layer for every LIVE slot, whatever implements them:

``index_key``   score EVERY cached token by its one index key. A cached token
                and layer cost
                    bytes:      index_head_dim x 2                  (256 at 128 bfloat16 values; fp8 as published would be 132)
                    operations: 2 x index_n_heads x index_head_dim  (8,192 at 32 heads: one dot product a head)
                counted by the tokens the index scored
                (``areal_decode_index_tokens_scored_total``).
``latent_row``  read the latent row ``[c | k_r]`` of every SELECTED token and
                put every query head against it. A selected token and layer
                cost
                    bytes:      (kv_lora_rank + qk_rope_head_dim) x 2             (1,152: the PUBLISHED row, not the stored one)
                    operations: 2 x heads x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)   (139,264 at 64 heads)
                counted by what the mathematics needs,
                min(index_topk, cached) a live slot
                (``areal_decode_latent_tokens_selected_total``): a form that
                fetches more rows than it selected (every page, the
                unselected masked) spends more time on the same count and
                reads LOWER, and none can read over 100.

The least time is max(bytes / peak bytes/s, operations / peak operations/s)
of the totals, over the device seconds of the ops under ``scopes`` inside the
runs of ``within_module``. The counters are read beside the chunk counter at
two instants inside the traced span: their ratio, tokens a chunk, times the
chunk programs the trace holds, those counted as their device seconds over
the median run's (``latent_cache_roofline`` reckons so too, and says why).
None where the program has no such counter or scope, or the configuration no
index."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics


def token_layer_cost(cfg: dict, what: str, bytes_per: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one token in one layer of a decode step, at
    the configuration's published sizes."""
    if what == "index_key":
        d = int(cfg["index_head_dim"])
        return 2 * int(cfg["index_n_heads"]) * d, d * bytes_per
    if what == "latent_row":
        row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
        return 2 * int(cfg["num_attention_heads"]) * (row + int(cfg["kv_lora_rank"])), row * bytes_per
    raise ValueError(f"unknown read {what!r}")


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or not facts["cfg"].get("index_topk"):
        return None
    tokens, chunks = counters.get(metric["tokens_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if tokens <= 0 or chunks <= 0:
        harness.log(f"{metric['tokens_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the {metric['what']} bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    ops_1, bytes_1 = token_layer_cost(facts["cfg"], metric["what"])
    n = tokens / chunks * n_calls
    r = peaks.roofline(n * ops_1, n * bytes_1, secs, peaks.peaks_for(facts["device_kind"]))
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); "
        f"{tokens / chunks:.0f} (token, layer) {metric['what']} reads a chunk ({tokens:.0f} over {chunks:.0f} chunks) x {bytes_1} B and {ops_1} operations: "
        f"least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
