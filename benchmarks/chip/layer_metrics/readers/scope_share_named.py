"""Share of device time spent under scope names that the METRIC FILE lists.

``scope_share`` reads the scopes of the vocabulary fixed in
``benchlib/trace_scopes.py``; a model family that adds scopes of its own (the
state-space mixer's ``ssm_proj``, ``ssm_conv``, ``ssm_state``,
``state_write``) is read here instead. The part is the device seconds of chip
0's leaf ops whose name-stack path holds any of ``scopes`` as a component; the
whole is the device seconds of the programs matching ``within_module`` (the
part is then taken inside their runs too), or with ``"base": "busy"`` the
chip's busy seconds. Each scope's seconds are logged alone. None, with the
reason logged, where the trace names none of the listed scopes' programs or no
listed scope at all (a program from before the names)."""
from benchlib import harness, trace_reduce, trace_scopes


def scope_seconds(facts: dict, scopes, within: str | None):
    """({scope: device s}, device s under any of them), or None where the
    run has no trace to read."""
    ops = trace_scopes.scoped_ops(facts, within=within)
    if ops is None:
        return None
    want = set(scopes)
    by: dict[str, float] = {}
    part = 0.0
    for d, p in ops:
        hit = want.intersection(trace_scopes._SPLIT.split(p))
        if hit:
            part += d
            for s in hit:
                by[s] = by.get(s, 0.0) + d
    return by, part


def read(metric: dict, facts: dict):
    within = metric.get("within_module")
    found = scope_seconds(facts, metric["scopes"], within)
    if found is None:
        return None
    by, part = found
    tr = facts["trace"]
    if metric.get("base") == "busy":
        whole = trace_reduce.busy_seconds(tr)
    else:
        whole, _ = trace_reduce.matched(tr, "modules", within)
    if whole <= 0:
        harness.log(f"no program matching {within!r} ran in the traced span" if within else "the device ran nothing")
        return None
    if not by:
        harness.log(f"no device op lies under any of {sorted(metric['scopes'])}: a program without these names")
        return None
    harness.log(
        f"scopes {sorted(by)} under {within or 'every program'}: {part:.4f} of {whole:.4f} device s; "
        + ", ".join(f"{k} {v:.4f} s ({100 * v / whole:.2f}%)" for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
    )
    return 100.0 * part / whole
