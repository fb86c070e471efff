"""Metrics of the program's span RECORD: every ``areal.*`` span and event of
the whole process, kept in memory by its one span primitive whether or not a
profiler session runs (``areal_tpu/utils/perf_tracer.py`` ``record()``), read
here after the run in the process that ran the engine. The device trace
covers ``trace_seconds`` of a window; the record covers set-up and every pass
or step of it.

The window's first instant is where the profiler session began (``Trace.t_min``;
readers run in traced runs only). The trace's clock is carried over to the
record's by the first spans both hold: same name, same start to within
``CLOCK_TOL_S`` and duration to within ``DUR_TOL_S`` (and ``RATE_TOL`` of the
time since the first span, and of the duration: the two clocks tick apart by
parts in a million), on the trace thread that holds most span time. Set-up is
[the record's process start, that instant]; the window runs ``window_s`` from
it (rollout kinds) or to the end of the record's last ``span`` (train: the
engine is gone before the reference check).

``mode``:
  setup   seconds of set-up inside the union, over every thread, of the spans
          named in ``spans`` (a name that ends in "." is a prefix); with
          ``complement`` the seconds of set-up inside none of them. ``detail``
          picks what is logged beside it: ``builds`` (count, the longest by
          program and key, the split into the ``areal.xla.*`` kinds and the
          rest), ``xla`` (counts by kind, inside and outside a build) or
          ``gaps`` (the longest uncovered gaps by their neighbours).
  excess  over the WHOLE window: sum over the ``span`` entries of
          max(0, duration - ``k`` x the window's median), in ms; logs every
          such span with its children's self times, its args and every record
          entry of any thread that overlaps it, and the programs built inside
          the window (expected: none).
None where the program keeps no record (the parent of the PR that brought it),
the record is empty, or the two clocks cannot be matched; never an error."""
import bisect

from benchlib import harness, trace_reduce, trace_scopes

CLOCK_TOL_S = 20e-6  # a span's TraceMe and its record entry read two clocks a microsecond or two apart
DUR_TOL_S = 100e-6  # and the TraceMe closes after the record's end is read and the last args (cpu_us) are set
RATE_TOL = 500e-6  # how far the two clocks' rates may lie apart (what NTP may slew a clock by)
MATCH_SPANS = 32
BUILD, XLA = "areal.program.build", "areal.xla."
XLA_KINDS = ("areal.xla.trace", "areal.xla.lower", "areal.xla.compile", "areal.xla.cache_load")


def the_record(facts: dict):
    """The process's span record (``facts["record"]`` where a test hands one)."""
    rec = facts.get("record")
    if rec is None:
        from areal_tpu.utils import perf_tracer

        take = getattr(perf_tracer.get_tracer(), "record", None)
        if take is None:
            harness.log("the program keeps no span record")
            return None
        rec = take()
    if not rec.entries:
        harness.log("the span record is empty")
        return None
    return rec


def clock_offset_s(spans: list, rec) -> float | None:
    """(record clock - trace clock) in seconds at the trace's start: the shift
    under which the first spans of the trace's busiest thread (``MATCH_SPANS``
    of them) are entries of the record, three in four of them in start AND
    duration. The two clocks need not tick alike (the profiler's is calibrated
    cycles, the record's ``CLOCK_MONOTONIC``): a duration, and a start's
    distance from the first span's, may differ by ``RATE_TOL`` of itself."""
    if not spans:
        harness.log("no areal.* span in the trace to match the record's clock by")
        return None
    by_thread: dict[str, float] = {}
    for s in spans:
        by_thread[s.thread] = by_thread.get(s.thread, 0.0) + s.dur_s
    loop = max(by_thread, key=by_thread.get)
    first = sorted((s for s in spans if s.thread == loop), key=lambda s: s.start_s)[:MATCH_SPANS]
    mine: dict[str, list[tuple[float, float]]] = {}  # name -> (start, duration) in seconds, by start
    for e in rec.entries:
        if not e.name.startswith(XLA):  # those were timed by jax: no TraceMe, not in the trace
            mine.setdefault(e.name, []).append((e.start_ns * 1e-9, (e.end_ns - e.start_ns) * 1e-9))
    for v in mine.values():
        v.sort()
    t_first = first[0].start_s

    def same_dur(dur: float, s) -> bool:
        return abs(dur - s.dur_s) <= DUR_TOL_S + RATE_TOL * s.dur_s

    def misfit(s, off: float) -> float | None:
        """How far the record's entry for ``s`` under ``off`` lies from it, in
        start and duration together; None where the record has none."""
        tol = CLOCK_TOL_S + RATE_TOL * (s.start_s - t_first)
        mine_s = mine.get(s.name, ())
        for start, dur in mine_s[bisect.bisect_left(mine_s, (s.start_s + off - tol,)) :]:
            if start > s.start_s + off + tol:
                break
            if same_dur(dur, s):
                return abs(start - s.start_s - off) + abs(dur - s.dur_s)
        return None

    # passes come at a near-regular beat, so a shift by one of them can fit
    # as many spans: of the shifts that fit three spans in four, the closest
    best = (0, 0.0, 0.0)  # (-spans fitted, their misfits' sum, shift)
    for start, dur in mine.get(first[0].name, ()):
        if same_dur(dur, first[0]):
            found = [d for s in first if (d := misfit(s, start - t_first)) is not None]
            best = min(best, (-len(found), sum(found), start - t_first))
    fitted = -best[0]
    if 4 * fitted >= 3 * len(first):
        harness.log(
            f"clocks matched by {fitted} of the trace's first {len(first)} spans on {loop} ({first[-1].start_s - t_first:.3f} s): "
            f"record - trace = {best[2]:.6f} s, mean misfit {best[1] / fitted * 1e6:.1f} us"
        )
        return best[2]
    harness.log(f"the record holds no run of spans like the trace's first {len(first)} on {loop} (the closest shift fits {fitted}): clocks not matched")
    return None


def window_start_ns(facts: dict, rec) -> int | None:
    """The window's first instant on the record's clock (worked out once a
    run: ``facts`` keeps it for the run's other metrics)."""
    if "span_record.window_start_ns" not in facts:
        sc = trace_scopes.for_run(facts)
        off = None if sc is None else clock_offset_s(sc.spans, rec)
        facts["span_record.window_start_ns"] = None if off is None else int((facts["trace"].t_min + off) * 1e9)
    return facts["span_record.window_start_ns"]


def _named(names: list[str]):
    exact = {n for n in names if not n.endswith(".")}
    prefixes = tuple(n for n in names if n.endswith("."))
    return lambda name: name in exact or (bool(prefixes) and name.startswith(prefixes))


def _clipped(entries, lo: int, hi: int) -> list[tuple[float, float]]:
    """Merged (start, end) seconds after ``lo`` of the entries' parts inside [lo, hi]."""
    return trace_reduce.union(
        [((max(e.start_ns, lo) - lo) * 1e-9, (min(e.end_ns, hi) - lo) * 1e-9) for e in entries if e.end_ns > lo and e.start_ns < hi and e.ph == "X"]
    )


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _inside(intervals, within) -> float:
    """Seconds of ``intervals`` that lie inside ``within`` (both merged, in order)."""
    out, j = 0.0, 0
    for s, e in intervals:
        while j < len(within) and within[j][1] <= s:
            j += 1
        k = j
        while k < len(within) and within[k][0] < e:
            out += max(0.0, min(e, within[k][1]) - max(s, within[k][0]))
            k += 1
    return out


def _log_builds(rec, lo: int, hi: int) -> None:
    builds = [e for e in rec.entries if e.name == BUILD and e.start_ns < hi and e.end_ns > lo]
    cover = _clipped(builds, lo, hi)
    kinds = {k: _clipped([e for e in rec.entries if e.name == k], lo, hi) for k in XLA_KINDS}
    inside = {k: _inside(v, cover) for k, v in kinds.items()}
    # a hit's cache read lies inside the areal.xla.compile it served
    inside["areal.xla.compile"] -= inside["areal.xla.cache_load"]
    total = _total(cover)
    longest = sorted(builds, key=lambda e: e.start_ns - e.end_ns)[:5]
    harness.log(
        f"{len(builds)} x {BUILD} before the window, {total:.3f} s in their union: "
        + ", ".join(f"{k.rsplit('.', 1)[-1]} {v:.3f}" for k, v in inside.items())
        + f", the rest (the first execution) {total - sum(inside.values()):.3f}; longest: "
        + "; ".join(f"{(e.args or {}).get('program')} {(e.args or {}).get('key')} {(e.end_ns - e.start_ns) * 1e-9:.3f} s" for e in longest)
    )


def _log_xla(rec, lo: int, hi: int) -> None:
    cover = _clipped([e for e in rec.entries if e.name == BUILD], lo, hi)
    parts = []
    outside: dict[str, float] = {}
    for k in XLA_KINDS:
        es = [e for e in rec.entries if e.name == k and e.end_ns > lo and e.start_ns < hi]
        u = _clipped(es, lo, hi)
        parts.append(f"{k.rsplit('.', 1)[-1]} {len(es)} ({_total(u):.3f} s, {_total(u) - _inside(u, cover):.3f} outside every {BUILD})")
        for e in es:
            mid = ((e.start_ns + e.end_ns) // 2 - lo) * 1e-9
            if k != "areal.xla.cache_load" and not any(s <= mid < t for s, t in cover):
                fun = str((e.args or {}).get("fun"))
                outside[fun] = outside.get(fun, 0.0) + (e.end_ns - e.start_ns) * 1e-9
    top = sorted(outside.items(), key=lambda kv: -kv[1])[:5]
    harness.log(
        "areal.xla.* before the window: " + ", ".join(parts)
        + ("; outside a build, by function: " + ", ".join(f"{f} {v:.3f} s" for f, v in top) if top else "")
    )


def _log_gaps(rec, covered, setup_s: float, lo: int, hi: int) -> None:
    spans = sorted((e for e in rec.entries if e.name.startswith("areal.") and e.ph == "X" and e.end_ns > lo and e.start_ns < hi), key=lambda e: e.start_ns)
    edges = [0.0] + [x for se in covered for x in se] + [setup_s]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]), reverse=True)[:5]

    def neighbour(t: float, before: bool) -> str:
        ns = lo + int(t * 1e9)
        near = [e for e in spans if (abs(e.end_ns - ns) if before else abs(e.start_ns - ns)) <= 1000]
        return near[0].name if near else ("process start" if before and t == 0.0 else "the window" if not before and t == setup_s else "?")

    harness.log(
        "longest gaps of set-up inside no areal.* span: "
        + "; ".join(f"{d:.3f} s at +{s:.3f} (after {neighbour(s, True)}, before {neighbour(e, False)})" for d, s, e in gaps)
    )


def _setup(metric: dict, facts: dict, rec) -> float | None:
    hi = window_start_ns(facts, rec)
    if hi is None:
        return None
    lo = rec.process_start_ns
    setup_s = (hi - lo) * 1e-9
    is_named = _named(metric["spans"])
    covered = _clipped([e for e in rec.entries if is_named(e.name)], lo, hi)
    inside = _total(covered)
    said = facts.get("values", {}).get("setup_s")
    harness.log(
        f"set-up by the record: {setup_s:.3f} s from the process's start to the window's first instant"
        + (f" (the run's setup_s {said:.3f})" if said is not None else "")
        + f"; inside {metric['spans']}: {inside:.3f} s"
    )
    detail = metric.get("detail")
    if detail == "builds":
        _log_builds(rec, lo, hi)
    elif detail == "xla":
        _log_xla(rec, lo, hi)
    elif detail == "gaps":
        _log_gaps(rec, covered, setup_s, lo, hi)
    return setup_s - inside if metric.get("complement") else inside


def _by_name(entries, t0: int, threads: dict) -> str:
    """Entries one by one, longest first; a name with more than three of them
    (the requests in flight) as a count and its longest."""
    by: dict[str, list] = {}
    for c in entries:
        by.setdefault(c.name, []).append(c)
    dur = lambda c: c.end_ns - c.start_ns  # noqa: E731
    parts = []
    for name, cs in sorted(by.items(), key=lambda kv: -max(map(dur, kv[1]))):
        if len(cs) > 3:
            parts.append(f"{name} x {len(cs)} (longest {max(map(dur, cs)) * 1e-6:.3f} ms)")
        else:
            parts += [f"{name} {dur(c) * 1e-6:.3f} ms at {(c.start_ns - t0) * 1e-6:+.3f} ({threads.get(c.thread, c.thread)}) {c.args or ''}".rstrip() for c in cs]
    return "; ".join(parts[:16]) or "none"


def _describe(rec, e, lo: int) -> str:
    """One slow span: its children's self times, and what overlaps it."""
    inside = lambda c: c.thread == e.thread and c.start_ns >= e.start_ns and c.end_ns <= e.end_ns  # noqa: E731
    over = [c for c in rec.entries if c is not e and c.end_ns > e.start_ns and c.start_ns < e.end_ns]
    kids = [trace_scopes.Span("", c.name, c.start_ns * 1e-9, (c.end_ns - c.start_ns) * 1e-9, {}) for c in [e] + over if inside(c) and c.ph == "X"]
    beside = [c for c in over if not inside(c)]
    own: dict[str, float] = {}
    for s, t, name in trace_scopes.innermost_segments(kids):
        own[name] = own.get(name, 0.0) + (t - s)
    return (
        f"at {(e.start_ns - lo) * 1e-9:+.3f} s: {(e.end_ns - e.start_ns) * 1e-6:.2f} ms {e.args or {}}; self ms by phase: "
        + (", ".join(f"{k.removeprefix('areal.')} {v * 1e3:.3f}" for k, v in sorted(own.items(), key=lambda kv: -kv[1])) or "no child span")
        + f"; {len(beside)} overlapping entries of other threads: " + _by_name(beside, e.start_ns, rec.threads)
    )


def _excess(metric: dict, facts: dict, rec) -> float | None:
    lo = window_start_ns(facts, rec)
    if lo is None:
        return None
    spans = [e for e in rec.entries if e.name == metric["span"] and e.start_ns >= lo]
    # the window runs window_s (rollout kinds) or to the end of the last span
    # (train: the engine is gone before the reference check)
    hi = lo + int(facts["window_s"] * 1e9) if "window_s" in facts else max((e.end_ns for e in spans), default=lo)
    spans = [e for e in spans if e.end_ns <= hi]
    if not spans:
        harness.log(f"no {metric['span']} inside the window in the record")
        return None
    durs = sorted(e.end_ns - e.start_ns for e in spans)
    med = durs[len(durs) // 2]
    k = float(metric["k"])
    slow = [e for e in spans if e.end_ns - e.start_ns > k * med]
    harness.log(
        f"{len(spans)} x {metric['span']} in the window of {(hi - lo) * 1e-9:.3f} s: median {med * 1e-6:.2f} ms, longest {durs[-1] * 1e-6:.2f} ms "
        f"({durs[-1] / med:.2f} x the median); {len(slow)} over {k} x the median"
    )
    # a rollout cell stops and parses its trace inside the window (the profiler session ended at
    # ``trace_span[1]`` on ``time.monotonic()``, the record's clock): a span that is slow there was held up by the benchmark itself
    stop_ns = int(facts["trace_span"][1] * 1e9) if facts.get("trace_span") else None
    own = lambda e: f" [began {(e.start_ns - stop_ns) * 1e-9:.1f} s after the benchmark started to stop and parse its trace]" if stop_ns is not None and e.start_ns >= stop_ns else ""  # noqa: E731
    for e in slow[:10]:
        harness.log(f"slow {metric['span']} " + _describe(rec, e, lo) + own(e))
    if not slow:  # what a sound window's longest is made of: what k has to leave room for
        longest = max(spans, key=lambda e: e.end_ns - e.start_ns)
        harness.log(f"longest {metric['span']} " + _describe(rec, longest, lo) + own(longest))
    built = [e for e in rec.entries if e.name == BUILD and e.end_ns > lo and e.start_ns < hi]
    harness.log(
        f"{len(built)} x {BUILD} inside the window"
        + "".join(f"; {(e.args or {}).get('program')} {(e.args or {}).get('key')} {(e.end_ns - e.start_ns) * 1e-6:.1f} ms at {(e.start_ns - lo) * 1e-9:+.3f} s" for e in built[:10])
    )
    return sum(max(0.0, (e.end_ns - e.start_ns) - k * med) for e in spans) * 1e-6


def read(metric: dict, facts: dict):
    if facts.get("trace") is None:
        return None
    rec = the_record(facts)
    if rec is None:
        return None
    mode = metric["mode"]
    if mode == "setup":
        return _setup(metric, facts, rec)
    if mode == "excess":
        return _excess(metric, facts, rec)
    raise ValueError(f"unknown mode {mode!r}")
