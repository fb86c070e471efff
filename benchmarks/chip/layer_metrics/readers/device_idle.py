"""Share of the traced window in which no op ran on the device."""
from benchlib import trace_reduce


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(tr) / tr.window_s)
