"""Metrics of the program's host spans (``areal.*``, written by its one span
primitive on the profiler's clock; ``benchlib/trace_scopes.py``).

``mode``:
  pass_less_child  mean over the ``span`` events of (the span - its ``child``
                   spans), in ms: with ``areal.decode.pass`` and its
                   ``device_wait`` it is the host work a decode chunk has to
                   hide. Logs each child's mean self time a pass, and the
                   longest pass with its phases.
  per_step         seconds inside ``span`` events a traced train step, in ms.
  idle_attributed  share (%) of chip 0's idle seconds, over the traced span,
                   that lie inside any span of the thread that holds most
                   span time (the engine's loop); logs the idle seconds by
                   innermost span name.
None where the trace holds no such span."""
from benchlib import harness, trace_reduce, trace_scopes


def _self_times(sc, parent) -> dict[str, float]:
    """Seconds by innermost span name inside ``parent`` (its own name: what
    no child covers)."""
    out: dict[str, float] = {}
    for s, e, name in trace_scopes.innermost_segments([parent] + trace_scopes.children(sc.spans, parent)):
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def _pass_less_child(metric: dict, sc) -> float | None:
    parents = [s for s in sc.spans if s.name == metric["span"]]
    if not parents:
        harness.log(f"no {metric['span']} span in the trace")
        return None
    rows = [(p, _self_times(sc, p)) for p in parents]
    less = [p.dur_s - t.get(metric["child"], 0.0) for p, t in rows]
    names = sorted({k for _, t in rows for k in t})
    means = {k: 1e3 * sum(t.get(k, 0.0) for _, t in rows) / len(rows) for k in names}
    longest, lt = max(rows, key=lambda r: r[0].dur_s)
    short = lambda k: k.rsplit(".", 1)[-1]  # noqa: E731
    harness.log(
        f"{len(parents)} x {metric['span']}: mean {1e3 * sum(p.dur_s for p in parents) / len(parents):.2f} ms; mean self ms by phase: "
        + ", ".join(f"{short(k)} {v:.3f}" for k, v in sorted(means.items(), key=lambda kv: -kv[1]))
    )
    harness.log(
        f"longest {metric['span']}: {1e3 * longest.dur_s:.2f} ms {longest.stats}: "
        + ", ".join(f"{short(k)} {1e3 * v:.3f}" for k, v in sorted(lt.items(), key=lambda kv: -kv[1]))
    )
    return 1e3 * sum(less) / len(less)


def _per_step(metric: dict, sc, facts: dict) -> float | None:
    steps = facts.get("traced_steps", 0)
    spans = [s for s in sc.spans if s.name == metric["span"]]
    if not spans or steps <= 0:
        harness.log(f"no {metric['span']} span in the trace" if not spans else "no traced step")
        return None
    total = sum(s.dur_s for s in spans)
    harness.log(f"{metric['span']}: {total:.4f} s in {len(spans)} spans over {steps} traced steps")
    return 1e3 * total / steps


def _idle_attributed(sc, tr) -> float | None:
    if not tr.devices or not sc.spans:
        harness.log("no device plane in the trace" if not tr.devices else "no areal.* span in the trace")
        return None
    by_thread: dict[str, float] = {}
    for s in sc.spans:
        by_thread[s.thread] = by_thread.get(s.thread, 0.0) + s.dur_s
    thread = max(by_thread, key=by_thread.get)
    segs = trace_scopes.innermost_segments([s for s in sc.spans if s.thread == thread])
    busy = trace_reduce.busy_intervals(tr.devices[0])
    edges = [tr.t_min] + [x for se in busy for x in se] + [tr.t_max]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle = sum(e - s for s, e in gaps)
    if idle <= 0:
        return None
    by: dict[str, float] = {}
    j = 0
    for gs, ge in gaps:  # both lists are in time order and non-overlapping
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            ov = min(ge, segs[k][1]) - max(gs, segs[k][0])
            if ov > 0:
                by[segs[k][2]] = by.get(segs[k][2], 0.0) + ov
            k += 1
    inside = sum(by.values())
    harness.log(
        f"chip 0 idle {1e3 * idle:.3f} ms in {len(gaps)} gaps, {1e3 * inside:.3f} ms inside spans of thread {thread}; idle ms by innermost span: "
        + ", ".join(f"{k} {1e3 * v:.3f}" for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
        + f"; outside every span {1e3 * (idle - inside):.3f}"
    )
    return 100.0 * inside / idle


def read(metric: dict, facts: dict):
    sc = trace_scopes.for_run(facts)
    if sc is None:
        return None
    mode = metric["mode"]
    if mode == "pass_less_child":
        return _pass_less_child(metric, sc)
    if mode == "per_step":
        return _per_step(metric, sc, facts)
    if mode == "idle_attributed":
        return _idle_attributed(sc, facts["trace"])
    raise ValueError(f"unknown mode {mode!r}")
