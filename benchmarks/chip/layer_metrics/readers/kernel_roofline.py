"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs (``benchlib/peaks.py``), over
the device time of the kernel's events in the trace.

For the paged decode kernel the work of one step is a function of the cached
tokens the decoding requests hold. Those come from the load generator's
records: a request decodes from its first token to its end and holds its
prompt plus the share of its output made so far (taken as linear in time);
the mean over the traced span of the sum over requests is the cached tokens
a step reads."""
from benchlib import harness, peaks, trace_reduce


def live_tokens(decoding, t_a: float, t_b: float, n: int = 400) -> float:
    """Mean over [t_a, t_b] of the tokens held by the requests decoding then."""
    total = 0.0
    for i in range(n):
        t = t_a + (t_b - t_a) * (i + 0.5) / n
        for first, end, prompt, out in decoding:
            if first <= t < end:
                total += prompt + out * (t - first) / (end - first)
    return total / n


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    if tr is None or not tr.devices:
        return None
    secs, n_ev = trace_reduce.matched(tr, "ops", metric["op_pattern"], within=metric.get("within_module"))
    if n_ev == 0:
        harness.log(f"no device op matches {metric['op_pattern']!r}: the kernel did not run (gather path?)")
        return None
    _, n_calls = trace_reduce.matched(tr, "modules", metric["steps_module_pattern"])
    steps = n_calls * int(facts["server"]["decode_steps"])
    span = facts.get("trace_span")
    if not span or steps == 0 or not facts.get("decoding"):
        return None
    live = live_tokens(facts["decoding"], span[0], span[1])
    ops, byts = getattr(peaks, metric["cost"])(facts["cfg"], live)
    r = peaks.roofline(ops * steps, byts * steps, secs, peaks.peaks_for(facts["device_kind"]))
    harness.log(
        f"{metric['op_pattern']!r}: {secs:.4f} device s in {n_ev} events over {steps} steps; "
        f"{live:.0f} cached tokens held by decoding requests on average; least time {r['least_s']:.4f} s, bound by {r['bound']}"
    )
    return r["pct"]
