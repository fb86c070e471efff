"""The stream mixes' share of their roofline in a prompt pass.

A model whose residual path is ``hc_mult`` = n streams of ``hidden_size`` = D
(manifold-constrained hyper-connections) mixes them before and after every
sublayer. For one (token, sublayer) the least a chip can do, whatever
implements the mix, is

    bytes:      3 x n x D x 2     (the streams read ONCE for the coefficients and the pre-mix,
                                   read once and written once by the post/res-mix: 12 x D x 2 at n = 4)
    operations: 2 x n D x (2n + n^2)  +  2 x n x (n + 2) x D
                (x' Phi, then the pre-mix's n, the res-mix's n^2 and the post-mix's n multiply-adds a channel)

and the least time is max(bytes / peak bytes/s, operations / peak operations/s)
of their totals (bytes bind: 86 KB against 0.86 M operations at n = 4, D =
3,584), over the device seconds of the ops under ``scopes`` (the ``mhc_*``
scopes) inside the runs of ``within_module`` (the prompt programs). The same
work whatever computes it: an implementation that passes over the streams more
often (a pass for the norm, one for ``Phi``, one a stream of the post-mix)
reads LOW, never high; one that moves exactly twice the least bytes reads 50.
The (token, sublayer) mixes come from the program's own counter
(``mixes_counter``: prompt tokens x sublayers, from the rows' lengths where the
prompt program is dispatched), read at two instants inside the traced span; a
prompt pass dispatched before an edge and run after it is counted on one side
and timed on the other, so with a handful of prompt passes a span the share may
read a pass high or low (the log line says how many prompt programs the trace
holds). None where the program has no such counter or scope, or the
configuration no ``hc_mult``."""
from benchlib import harness, peaks

import importlib.util
import os
import re


def mix_cost(cfg: dict, bytes_per: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one (token, sublayer) stream mix at the
    configuration's published sizes."""
    n, D = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    ops = 2 * n * D * (2 * n + n * n) + 2 * n * (n + 2) * D
    return ops, 3 * n * D * bytes_per


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or int(facts["cfg"].get("hc_mult") or 0) < 2:
        return None
    mixes = counters.get(metric["mixes_counter"], 0.0)
    if mixes <= 0:
        harness.log(f"{metric['mixes_counter']} did not move in the traced span: a program without the counter, or no prompt pass")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the streams' bytes")
        return None
    secs = found[1]
    if secs <= 0:
        return None
    rx = re.compile(metric["within_module"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    ops_1, bytes_1 = mix_cost(facts["cfg"])
    r = peaks.roofline(mixes * ops_1, mixes * bytes_1, secs, peaks.peaks_for(facts["device_kind"]))
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} prompt programs; {mixes:.3e} (token, sublayer) mixes x "
        f"{bytes_1} B and {ops_1} operations: least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
