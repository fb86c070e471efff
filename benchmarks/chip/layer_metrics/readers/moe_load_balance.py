"""How unevenly a layer's experts are loaded: the busiest expert's rows over
the mean, in the worst expert layer, over the window.

From the program's ``/statusz`` field ``status`` . ``field`` (cumulative rows
of live slots every expert of every expert layer got from decode steps,
[expert layers][experts]) at the window's two edges. 1.0 = every expert the
same load; the busiest expert is what a layer sharded over chips would wait
for. None where the program has no such field."""
from benchlib import harness


def read(metric: dict, facts: dict):
    edges = (facts.get("extra") or {}).get("status")
    if not edges:
        return None
    loads = [(e or {}).get(metric["status"]) for e in edges]
    if any(not ld or metric["field"] not in ld for ld in loads):
        return None
    first, last = (ld[metric["field"]] for ld in loads)
    worst = None
    for a, b in zip(first, last):
        rows = [y - x for x, y in zip(a, b)]
        if sum(rows) > 0:
            ratio = max(rows) * len(rows) / sum(rows)
            worst = ratio if worst is None else max(worst, ratio)
    if worst is not None:
        harness.log(f"{metric['status']}.{metric['field']}: {len(last)} expert layers x {len(last[0])} experts; the busiest expert over the mean, worst layer: {worst:.3f}")
    return worst
