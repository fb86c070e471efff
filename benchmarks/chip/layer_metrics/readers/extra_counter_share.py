"""Share of one sum of counter deltas in another, over the window, for the
counters a cell names in its ``family`` block (``facts["extra"]``:
``rollout_family.read_extra``), as ``counter_share`` has it for the rollout
kind's own. None where the whole did not move: a program without the
counters, or a cell that does not name them."""


def read(metric: dict, facts: dict):
    c = (facts.get("extra") or {}).get("window_counters") or {}
    whole = sum(c.get(k, 0.0) for k in metric["whole"])
    if whole <= 0:
        return None
    return 100.0 * sum(c.get(k, 0.0) for k in metric["part"]) / whole
