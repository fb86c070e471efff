"""Device seconds of the jitted programs whose name matches
``module_pattern``, per unit of work done in the traced span:
  decode_step   events x the engine's decode steps a call
  prefill_ktok  thousands of prompt tokens computed (counter delta)
  train_step    train steps run while the trace was on
Reported in milliseconds."""
from benchlib import harness, trace_reduce


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    if tr is None or not tr.devices:
        return None
    secs, n = trace_reduce.matched(tr, "modules", metric["module_pattern"])
    if n == 0:
        harness.log(f"no program matching {metric['module_pattern']!r} ran in the traced span")
        return None
    per = metric["per"]
    if per == "decode_step":
        units = n * int(facts["server"]["decode_steps"])
    elif per == "prefill_ktok":
        units = (facts.get("trace_counters") or {}).get("areal_decode_prefill_tokens_total", 0) / 1e3
    elif per == "train_step":
        units = facts.get("traced_steps", 0)
    else:
        raise ValueError(f"unknown unit of work {per!r}")
    if units <= 0:
        return None
    harness.log(f"{metric['module_pattern']!r}: {secs:.4f} device s over {n} program runs, {units:g} x {per}")
    return 1e3 * secs / units
