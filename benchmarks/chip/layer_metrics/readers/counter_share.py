"""Share of one sum of counter deltas in another, over the window."""


def read(metric: dict, facts: dict):
    c = facts.get("counters") or {}
    whole = sum(c.get(k, 0.0) for k in metric["whole"])
    if whole <= 0:
        return None
    return 100.0 * sum(c.get(k, 0.0) for k in metric["part"]) / whole
