"""The recurrent-state update's share of its memory roofline.

A decode step of a model with state-space layers has to read and write the
recurrent state of every LIVE slot once a layer: that is the least it can do,
whatever the program does with the slots that hold no request. The least time
is therefore

    mean live slots x steps x 2 x state_bytes_per_slot(cfg) / peak bytes/s

over the device seconds of the ops under ``scopes`` (the recurrence's scope)
inside the runs of ``within_module``. Live slots are the ``/statusz``
``active_slots`` samples taken inside the traced span; steps are the runs of
``steps_module_pattern`` x the engine's decode steps a call. Bound: memory
(the update is a few operations a byte). A program that also reads or writes
the state of slots without a request shows a smaller share, which is the
point of the number."""
from benchlib import harness, peaks, trace_reduce

import importlib.util
import os

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of one slot's recurrent state over all state-space layers, in the
    types the configuration file states: per layer the SSM state
    [mamba_n_heads, mamba_d_head, mamba_d_state] and the conv window of the
    last ``mamba_d_conv - 1`` inputs over d_inner + 2 * groups * d_state
    channels."""
    a = cfg.get("assumed", {})
    n_layers = sum(1 for t in cfg["layer_types"] if t == "mamba")
    h, p, n, g = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    ssm = h * p * n * _DTYPE_BYTES[a.get("ssm_state_dtype", "float32")]
    conv = (h * p + 2 * g * n) * (int(cfg["mamba_d_conv"]) - 1) * _DTYPE_BYTES[a.get("conv_state_dtype", "bfloat16")]
    return n_layers * (ssm + conv)


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    if tr is None or not tr.devices or "layer_types" not in facts["cfg"]:
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the state's bytes")
        return None
    secs = found[1]
    _, n_calls = trace_reduce.matched(tr, "modules", metric["steps_module_pattern"])
    steps = n_calls * int(facts["server"]["decode_steps"])
    span = facts.get("trace_span")
    live = [g["active_slots"] for g in facts.get("gauges", []) if span and span[0] <= g["t"] <= span[1]]
    if not live or steps == 0 or secs <= 0:
        return None
    mean_live = sum(live) / len(live)
    per_slot = state_bytes_per_slot(facts["cfg"])
    least = mean_live * steps * 2 * per_slot / peaks.peaks_for(facts["device_kind"])["hbm_bytes_s"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {steps} steps; {mean_live:.1f} live slots on average "
        f"({len(live)} samples) x {per_slot / 1e6:.1f} MB of state a slot, read and written: least time {least:.4f} s, bound by memory"
    )
    return 100.0 * least / secs
