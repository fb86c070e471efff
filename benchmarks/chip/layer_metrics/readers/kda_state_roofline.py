"""The ``kda`` state update's share of its memory roofline in a decode step.

A decode step of a model with ``kda`` layers (a delta rule whose state decays
by a factor of its own every key channel) has to read and write the matrix
state of every LIVE slot once a layer: that is the least it can do, whatever
implements the update and whatever it does with the slots that hold no
request. The least time is

    (slot, layer) updates in the traced span x 2 x state_bytes(cfg) / peak bytes/s

over the device seconds of the ops under ``scopes`` (the recurrence's scope)
inside the runs of ``within_module``. The updates come from the program's own
counter (``updates_counter``: counted on the device for live slots only,
where the kernel walks them), read beside the chunk counter at two instants
inside the traced span, and the chunk programs are counted as their device
seconds over a whole run's: the arithmetic of ``gdn_state_roofline.py``
beside this file, which reads another family's configuration keys. Bound:
memory (some ten operations a byte). The scope also holds the gated norm on
the recurrence's output, which moves no state: the share reads lower for it,
not higher. None where the program has no such counter or scope, or the
configuration no ``linear_attn_config``."""
from benchlib import harness, peaks

import importlib.util
import os
import re
import statistics

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def state_bytes(cfg: dict) -> int:
    """Bytes of one slot's ``kda`` state in one layer, at the configuration's
    published sizes and in the type its file states: [num_heads, head_dim
    (keys), head_dim (values)] of ``linear_attn_config``."""
    lin = cfg["linear_attn_config"]
    n = int(lin["num_heads"]) * int(lin["head_dim"]) ** 2
    return n * _DTYPE_BYTES[cfg.get("assumed", {}).get("kda_state_dtype", "float32")]


def _scope_seconds():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_scope_share_named", os.path.join(here, "scope_share_named.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_seconds


def read(metric: dict, facts: dict):
    tr = facts.get("trace")
    counters = (facts.get("extra") or {}).get("trace_counters")
    if tr is None or not tr.devices or not counters or "linear_attn_config" not in facts["cfg"]:
        return None
    updates, chunks = counters.get(metric["updates_counter"], 0.0), counters.get(metric["chunks_counter"], 0.0)
    if updates <= 0 or chunks <= 0:
        harness.log(f"{metric['updates_counter']} did not move in the traced span: a program without the counter")
        return None
    found = _scope_seconds()(facts, metric["scopes"], metric.get("within_module"))
    if found is None or not found[0]:
        harness.log(f"no device op lies under {metric['scopes']}: nothing to set against the state's bytes")
        return None
    secs = found[1]
    rx = re.compile(metric["steps_module_pattern"])
    runs = [dur for name, _, dur in tr.devices[0].modules if rx.search(name)]
    if not runs or secs <= 0:
        return None
    n_calls = sum(runs) / statistics.median(runs)  # whole runs' worth of chunk programs
    per_update = 2 * state_bytes(facts["cfg"])
    least = updates / chunks * n_calls * per_update / peaks.peaks_for(facts["device_kind"])["hbm_bytes_s"]
    harness.log(
        f"scopes {metric['scopes']}: {secs:.4f} device s over {len(runs)} chunk programs ({n_calls:.2f} whole runs' worth); "
        f"{updates / chunks:.1f} (slot, layer) updates a chunk ({updates:.0f} over {chunks:.0f} chunks) x {per_update / 1e6:.2f} MB "
        f"read and written: least time {least:.4f} s, bound by memory"
    )
    return 100.0 * least / secs
