"""Share of device time spent under the program's own scope names.

The program gives every op of its forwards a ``jax.named_scope`` from one
vocabulary (``benchlib/trace_scopes.py`` VOCABULARY); the profiler records each
op's name-stack path. The part is the device seconds of chip 0's leaf ops
whose path lies under any of ``scopes`` (the whole vocabulary where the metric
names none), or with ``stack_has`` holds that component
(``rematted_computation`` marks what a ``jax.checkpoint`` runs again in the
backward pass). The whole is the device seconds of the programs matching
``within_module``, over which the part is then taken too, or with
``"base": "busy"`` the chip's busy seconds. Each scope's seconds are logged
alone. None, with the reason logged, where the trace names no scope at all (a
program from before the names)."""
from benchlib import harness, trace_reduce, trace_scopes


def read(metric: dict, facts: dict):
    within = metric.get("within_module")
    ops = trace_scopes.scoped_ops(facts, within=within)
    if ops is None:
        return None
    tr = facts["trace"]
    if metric.get("base") == "busy":
        whole = trace_reduce.busy_seconds(tr)
    else:
        whole, _ = trace_reduce.matched(tr, "modules", within)
    if whole <= 0:
        harness.log(f"no program matching {within!r} ran in the traced span" if within else "the device ran nothing")
        return None
    if not any(trace_scopes.scopes_of(p) for _, p in ops):
        harness.log("no device op carries a scope of the program's vocabulary: a program from before the names")
        return None
    by: dict[str, float] = {}
    part = 0.0
    if "stack_has" in metric:
        part = sum(d for d, p in ops if metric["stack_has"] in p.split("/"))
        by[metric["stack_has"]] = part
    else:
        want = set(metric.get("scopes", trace_scopes.VOCABULARY))
        for d, p in ops:
            hit = trace_scopes.scopes_of(p) & want
            if hit:
                part += d
                for s in hit:
                    by[s] = by.get(s, 0.0) + d
    harness.log(
        f"scopes {sorted(by)} under {within or 'every program'}: {part:.4f} of {whole:.4f} device s; "
        + ", ".join(f"{k} {v:.4f} s ({100 * v / whole:.2f}%)" for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
    )
    return 100.0 * part / whole
