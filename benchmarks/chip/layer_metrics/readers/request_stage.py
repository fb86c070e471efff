"""Median of one stat of the program's request-stage events, in ms.

The decode engine marks each request's stages; at the first token it writes
one point event (``areal.request.first_token``) whose stats split the time to
that token: ``queue_wait_us`` (receipt to admission), ``prefill_us`` (the
prefill's dispatch) and ``since_prefill_end_us`` (from there to the drain of
the chunk that held the token). ``event`` and ``stat`` pick one; the count of
events in the traced span is logged with all three medians."""
from benchlib import harness, trace_scopes


def read(metric: dict, facts: dict):
    sc = trace_scopes.for_run(facts)
    if sc is None:
        return None
    evs = [s.stats for s in sc.spans if s.name == metric["event"] and metric["stat"] in s.stats]
    if not evs:
        harness.log(f"no {metric['event']} event with {metric['stat']} in the trace")
        return None
    med = lambda k: harness.percentile([float(e[k]) for e in evs if k in e], 50) / 1e3  # noqa: E731
    keys = sorted({k for e in evs for k in e if k.endswith("_us")})
    harness.log(f"{len(evs)} x {metric['event']} in the traced span; medians ms: " + ", ".join(f"{k[:-3]} {med(k):.2f}" for k in keys))
    return med(metric["stat"])
