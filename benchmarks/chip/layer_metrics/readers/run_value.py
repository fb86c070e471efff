"""A value the run itself read beside its end-to-end metrics (``metric["value"]``
names it), reported as a per-layer metric: a statistic too unsteady to carry a bound."""


def read(metric: dict, facts: dict):
    return (facts.get("values") or {}).get(metric["value"])
