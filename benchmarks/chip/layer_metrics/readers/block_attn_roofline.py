"""The in-block attention launch's share of its roofline in a block pass.

A pass of a block-diffusion model puts the B rows of every live slot's block
against the keys and values of the slot's committed blocks, once a layer. The
launch fetches them in whole blocks of pages, and what it fetched is counted
where it is decided (``tokens_counter``: cached tokens fetched for live
slots' passes, a pass: a layer's K and V of every KV head ride on each). A
fetched token and layer cost

    bytes:      2 x num_key_value_heads x head_dim x 2            (K and V, bfloat16: 2,048 at 4 heads of 128)
    operations: 2 x 2 x block_length x num_attention_heads x head_dim   (scores and values for the block's rows: 65,536 at 4 rows x 32 heads of 128)

and the least time is max(bytes / peak bytes/s, operations / peak
operations/s) of their totals (x ``num_hidden_layers``), over the device
seconds of the launch inside the runs of ``within_module``: the leaf ops under
``scopes`` whose name-stack path matches ``op_pattern`` (the Pallas launch by
its name); where none matches, every op under ``scopes`` (the gather path:
the gathers, the concatenation and the products), and the log says so. The
in-flight block's own B keys are left out of the cost (B of hundreds of
tokens): the share reads low by that, never high. The fetched tokens a chunk
come from the counter beside the chunk counter at two instants inside the
traced span, times the chunk programs the trace holds, counted as their device
seconds over the median run's (``latent_cache_roofline`` reckons so too). None
where the program has no such counter or scope."""
from benchlib import harness, peaks, trace_scopes

import re
import statistics


def token_layer_cost(cfg: dict, bytes_per: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one fetched cached token in one layer of a
    block pass, at the configuration's published sizes."""
    hd = int(cfg["head_dim"])
    rows = int(cfg["assumed"]["block_length"])
    return 2 * 2 * rows * int(cfg["num_attention_heads"]) * hd, 2 * int(cfg["num_key_value_heads"]) * hd * bytes_per


def launch_seconds(facts: dict, scopes, op_pattern: str, within: str | None):
    """(device seconds of the launch, "kernel" or "scope"), or None where the
    trace names none of ``scopes``."""
    ops = trace_scopes.scoped_ops(facts, within=within)
    if ops is None:
        return None
    want, rx = set(scopes), re.compile(op_pattern)
    under = [(d, p) for d, p in ops if want.intersection(trace_scopes._SPLIT.split(p))]
    if not under:
        return None
    named = [d for d, p in under if rx.search(p)]
    return (sum(named), "kernel") if named else (sum(d for d, _ in under), "scope")


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or "block_length" not in (facts["cfg"].get("assumed") or {}):
        return None
    reads, chunks = counters.get(metric["tokens_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if reads <= 0 or chunks <= 0:
        harness.log(f"{metric['tokens_counter']} did not move in the traced span: a program without the counter")
        return None
    found = launch_seconds(facts, metric["scopes"], metric["op_pattern"], metric.get("within_module"))
    if found is None or found[0] <= 0:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the fetched tokens' bytes")
        return None
    secs, how = found
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    ops_1, bytes_1 = token_layer_cost(facts["cfg"])
    n = reads / chunks * n_calls * int(facts["cfg"]["num_hidden_layers"])
    r = peaks.roofline(n * ops_1, n * bytes_1, secs, peaks.peaks_for(facts["device_kind"]))
    harness.log(
        f"in-block attention ({'the Pallas launch ' + metric['op_pattern'] if how == 'kernel' else 'NO op matches ' + metric['op_pattern'] + ': every op under ' + str(metric['scopes']) + ', the gather path'}): "
        f"{secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); {reads / chunks:.0f} cached tokens fetched a chunk "
        f"({reads:.0f} over {chunks:.0f} chunks) x {facts['cfg']['num_hidden_layers']} layers x {bytes_1} B and {ops_1} operations: least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
