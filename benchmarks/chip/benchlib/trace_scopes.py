"""What a traced run's ``.xplane.pb`` says in the program's own names.

``trace_reduce`` reduces the trace by what the runtime calls things (HLO op
names, jitted programs, runtime threads). This module adds the two kinds of
name the program gives itself:

  scopes  the ``jax.named_scope`` path of every device op (``jit(chunk)/
          while/body/.../attn/...``). The profiler keeps it in the op's event
          *metadata* (stat ``tf_op``, ``<path>:<op type>``), which
          ``jax.profiler.ProfileData`` does not expose, so the metadata map is
          read from the protobuf wire format (layout: ``tools/cut_xplane.py``).
  spans   the ``areal.*`` host events the program's one span primitive writes
          (``areal_tpu/utils/perf_tracer.py``), with the stats they carry, on
          the same clock as the device ops.

``facts`` carries the loaded ``Trace`` but no path: the run's file is the
newest under ``<checkout>/.bench_tmp/*/trace/`` (``harness.Tracer`` writes
there), and is taken only if it starts with the same host event as the
``Trace`` in ``facts``. A program with no scopes or spans (the parent of the PR
that brought them) gives empty results, never an error.
"""

from __future__ import annotations

import bisect
import functools
import glob
import importlib.util
import os
import re
from dataclasses import dataclass

from benchlib import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))

# the program's scope vocabulary (areal_tpu/models/qwen.py SCOPES, plus the
# engines' sampler / loss / optimizer)
VOCABULARY = ("embed", "attn_proj", "kv_write", "attn", "mlp", "lm_head", "sampler", "loss", "optimizer")
SPAN_PREFIX = "areal."
_SPLIT = re.compile(r"[/()]+")


def _wire():
    """``tools/cut_xplane.py``'s wire-format reader (a script, not a package)."""
    spec = importlib.util.spec_from_file_location("benchfile_cut_xplane", os.path.join(os.path.dirname(HERE), "tools", "cut_xplane.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.fields


@dataclass
class Span:
    thread: str  # the host line, unique within the trace
    name: str
    start_s: float
    dur_s: float
    stats: dict

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


@dataclass
class Scoped:
    path: str
    op_paths: dict[str, dict[str, str]]  # device plane -> op name -> name-stack path
    spans: list[Span]  # the program's host events, by start
    first_host: tuple | None  # (thread, name, start_s) of the file's first host event


def newest_xplane(root: str = ROOT) -> str | None:
    files = glob.glob(os.path.join(root, ".bench_tmp", "*", "trace", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _op_paths(buf: bytes) -> dict[str, dict[str, str]]:
    """{device plane: {op name: name-stack path}} from XSpace bytes.

    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5 (maps:
    key=1, value=2); XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1, .str_value=5, .ref_value=7 (a stat_metadata id whose
    name is the string)."""
    fields = _wire()
    out: dict[str, dict[str, str]] = {}
    for fno, wt, plane, _ in fields(buf):
        if fno != 1 or wt != 2:
            continue
        name, metas, stat_names = "", [], {}
        for f, w, v, _ in fields(plane):
            if f == 2 and w == 2:
                name = v.decode(errors="replace")
            elif f == 4 and w == 2:
                metas.append(v)
            elif f == 5 and w == 2:
                key = sname = None
                for f2, w2, v2, _ in fields(v):
                    if f2 == 1 and w2 == 0:
                        key = v2
                    elif f2 == 2 and w2 == 2:
                        sname = next((x.decode(errors="replace") for f3, w3, x, _ in fields(v2) if f3 == 2 and w3 == 2), None)
                stat_names[key] = sname
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        paths: dict[str, str] = {}
        for entry in metas:
            meta = next((v for f, w, v, _ in fields(entry) if f == 2 and w == 2), None)
            if meta is None or tf_op is None:
                continue
            op_name, path = None, None
            for f, w, v, _ in fields(meta):
                if f == 2 and w == 2:
                    op_name = v.decode(errors="replace")
                elif f == 5 and w == 2:
                    st = {f2: v2 for f2, w2, v2, _ in fields(v)}
                    if st.get(1) == tf_op:
                        path = st[5].decode(errors="replace") if 5 in st else stat_names.get(st.get(7))
            if op_name is not None and path:
                paths[op_name] = path.rpartition(":")[0] or path
        out[name] = paths
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> Scoped:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    op_paths = _op_paths(buf)
    spans, first = [], None
    for plane in ProfileData.from_serialized_xspace(buf).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for ev in line.events:
                if first is None:
                    first = (line.name, ev.name, ev.start_ns * 1e-9)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(thread, ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, dict(ev.stats)))
    spans.sort(key=lambda s: (s.start_s, -s.dur_s))
    return Scoped(path, op_paths, spans, first)


def for_run(facts: dict) -> Scoped | None:
    """The scopes and spans of this run's trace, or None (with the reason
    logged) where the run was not traced or its file cannot be told.
    ``facts["xplane"]`` names the file where a test hands one."""
    tr = facts.get("trace")
    if tr is None:
        return None
    path = facts.get("xplane") or newest_xplane()
    if path is None:
        harness.log("no .xplane.pb under .bench_tmp/*/trace: nothing to read scopes and spans from")
        return None
    sc = load(path)
    head = tr.host[0][:3] if tr.host else None
    if sc.first_host != head:
        harness.log(f"{path} is not this run's trace (it starts with {sc.first_host}, the run's with {head})")
        return None
    return sc


def scopes_of(path: str) -> set[str]:
    """The vocabulary's scopes a name-stack path lies under. Autodiff wraps a
    scope's name (``transpose(jvp(attn))``), so components are split at
    brackets too."""
    return set(_SPLIT.split(path)).intersection(VOCABULARY)


def scoped_ops(facts: dict, within: str | None = None):
    """(seconds, path) of every leaf device op of chip 0, the path "" where
    the trace names none; ``within`` keeps the ops that start inside a run
    of a program matching it. None where there is nothing to read."""
    sc = for_run(facts)
    tr = facts.get("trace")
    if sc is None or not tr.devices:
        return None
    dev = tr.devices[0]
    paths = sc.op_paths.get(dev.name, {})
    ops = trace_reduce.leaf_ops(dev)
    if within is not None:
        rx = re.compile(within)
        runs = trace_reduce.union([(s, s + d) for n, s, d in dev.modules if rx.search(n)])
        ops = [op for op in ops if _inside(runs, op[1])]
    return [(d, paths.get(n, "")) for n, _, d in ops]


def _inside(intervals: list[tuple[float, float]], t: float) -> bool:
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and t < intervals[i][1]


def children(spans: list[Span], parent: Span) -> list[Span]:
    """The spans on the parent's thread that lie inside it."""
    return [
        s for s in spans
        if s is not parent and s.thread == parent.thread and s.start_s >= parent.start_s and s.end_s <= parent.end_s
    ]


def innermost_segments(spans: list[Span]) -> list[tuple[float, float, str]]:
    """One thread's nested spans flattened to (start, end, name of the
    innermost span open then), in time order, gaps between spans left out."""
    out: list[tuple[float, float, str]] = []
    stack: list[Span] = []
    t = 0.0

    def emit(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1].name))
        t = max(t, upto)

    for s in sorted(spans, key=lambda s: (s.start_s, -s.dur_s)):
        while stack and stack[-1].end_s <= s.start_s:
            emit(stack[-1].end_s)
            stack.pop()
        emit(s.start_s)
        stack.append(s)
    while stack:
        emit(stack[-1].end_s)
        stack.pop()
    return out
