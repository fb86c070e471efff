"""The latent cache read's share of its roofline in a decode step.

A decode step of a latent-attention (MLA) model has to read, once a layer,
the latent row ``[c | k_r]`` of every cached token of every LIVE slot, and to
put every query head against it: that is the least it can do, whatever the
page stores beside the row and whatever implements the product. A cached
token and layer cost

    bytes:      (kv_lora_rank + qk_rope_head_dim) x 2          (1,152 at 512 + 64, bfloat16: the PUBLISHED row, not the stored one)
    operations: 2 x heads x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)   (scores over the row, values over the latent: 69,632 at 32 heads)

and the least time is max(bytes / peak bytes/s, operations / peak
operations/s) of their totals, over the device seconds of the ops under
``scopes`` (the latent kernel's scope) inside the runs of ``within_module``.
Padding lanes, a page read twice or operands in a wider type read LOW,
never high. The (token, layer) reads come from the program's own counter
(``tokens_counter``: cached tokens of live slots x latent layers, counted on
the device where the kernel walks them), read beside the chunk counter at two
instants inside the traced span: their ratio, reads a chunk, times the chunk
programs the trace holds (the counters are credited when a chunk drains, the
trace holds the chunks that ran: the ratio carries over, the instants need
not match). The trace cuts the first and the last chunk program it sees: the
programs are counted as their device seconds over a whole run's (the median
run's), not by number (``gdn_state_roofline`` reckons so too). None where the
program has no such counter or scope, or the configuration no latent."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics


def token_layer_cost(cfg: dict, bytes_per: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one cached token in one latent-attention layer
    of a decode step, at the configuration's published sizes."""
    row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    ops = 2 * int(cfg["num_attention_heads"]) * (row + int(cfg["kv_lora_rank"]))
    return ops, row * bytes_per


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or "kv_lora_rank" not in facts["cfg"]:
        return None
    reads, chunks = counters.get(metric["tokens_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if reads <= 0 or chunks <= 0:
        harness.log(f"{metric['tokens_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the latent rows' bytes")
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
        f"{reads / chunks:.0f} (cached token, layer) reads a chunk ({reads:.0f} over {chunks:.0f} chunks) x {bytes_1} B and {ops_1} operations: "
        f"least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
