"""Chrome-trace performance tracer + per-rollout session tracer.

Plays the role of reference areal/utils/perf_tracer.py (2,123 LoC): emits
catapult JSON ("traceEvents") viewable in chrome://tracing or Perfetto, plus
a JSONL of rollout-session lifecycles. Cross-async propagation uses
ContextVars, so events recorded inside workflow coroutines attach to the
right task/session (reference :28-38).

``trace_scope`` is the program's ONE span primitive (docs/observability.md
"Spans and scopes"): every span also enters a ``jax.profiler.TraceAnnotation``
(a TraceMe: a flag check while no profiler session runs), so a device profile
(``start_device_profile`` / ``POST /debug/profile``) carries the program's
spans on the same clock as the device's ops. A process that has not imported
jax emits no annotation and is not made to import it.

Every span and instant also lands in the tracer's bounded in-memory RECORD
(one tuple appended to a ``deque(maxlen=max_events)``), for the whole life of
the process and whether or not a profiler session runs: set-up, and every
pass and step of a run, can be read after the fact (``record()``; the chip
benchmark's ``span_record`` reader; ``SlowSpanWatch``'s WARNING line).
``PerfTracerConfig.enabled`` decides only whether ``save()`` writes the
record out as a Chrome trace.

Surface:
    configure(cfg, rank=..., role=...)      process-level setup (keeps the record)
    trace_scope(name, category=..., args=)  sync context manager (``Span``)
    atrace_scope(name, ...)                 async context manager
    instant(name, ...)                      point event
    record()                                snapshot of the record (``SpanRecord``)
    SlowSpanWatch(name)                     WARNING line for a span over 3 x its median
    save(step=..., force=...)               periodic/final flush
    SessionTracer                           rollout lifecycle records
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import json
import os
import statistics
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, NamedTuple

from areal_tpu.api.config import PerfTracerConfig
from areal_tpu.utils import logging as alog

logger = alog.getLogger("perf_tracer")


class Category(str, Enum):
    COMPUTE = "compute"
    COMM = "comm"
    IO = "io"
    SCHEDULER = "scheduler"
    INSTR = "instr"


_task_id_var: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "areal_tpu_trace_task", default=None
)
_session_id_var: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "areal_tpu_trace_session", default=None
)


def set_task_context(task_id: str | None = None, session_id: str | None = None):
    if task_id is not None:
        _task_id_var.set(task_id)
    if session_id is not None:
        _session_id_var.set(session_id)


def get_task_context() -> tuple[str | None, str | None]:
    """(task_id, session_id) of the calling context — the payload that
    observability.tracecontext rides across RPC/HTTP hops."""
    return _task_id_var.get(), _session_id_var.get()


def clear_task_context() -> None:
    """Unconditionally reset both ids in the calling context. Inbound
    request handlers must call this when no trace header arrived: aiohttp
    serves a keep-alive connection's requests from one task, so stale ids
    would otherwise leak into later requests' spans."""
    _task_id_var.set(None)
    _session_id_var.set(None)


def _process_start_ns() -> int:
    """The process's start on ``time.monotonic_ns()``'s clock: its
    ``starttime`` in ``/proc/self/stat`` (clock ticks after boot, so the
    interpreter's own start and the imports before the first span count),
    carried over by the boot-time clock. Where that cannot be read, now."""
    now = time.monotonic_ns()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - age if 0 <= age <= now else now


PROCESS_START_NS = _process_start_ns()


class RecordEntry(NamedTuple):
    """One entry of the record. Times are ``time.monotonic_ns()``; an
    instant (``ph`` "i") has ``end_ns == start_ns``; ``thread`` is
    ``threading.get_ident()`` of the thread that ended it."""

    name: str
    start_ns: int
    end_ns: int
    thread: int
    args: dict | None
    category: Any
    ph: str


@dataclass
class SpanRecord:
    """A snapshot of a tracer's record (``PerfTracer.record()``)."""

    process_start_ns: int
    entries: list[RecordEntry]  # in the order they ended
    threads: dict[int, str]  # names of the threads alive at the snapshot


class PerfTracer:
    """One process's span record, and its Chrome-trace (catapult JSON) writer."""

    def __init__(self, config: PerfTracerConfig, rank: int = 0, role: str | None = None, events: deque | None = None):
        self.config = config
        self.enabled = config.enabled
        self.rank = rank
        self.role = role
        # the record: RecordEntry-shaped plain tuples, appended without a lock
        # (deque.append is atomic) and bounded: the newest max_events stay
        cap = config.max_events or None
        self._events: deque[tuple] = events if events is not None and events.maxlen == cap else deque(events or (), maxlen=cap)
        self._pid = os.getpid()
        self._last_save_step = -1

    # -- event emission ----------------------------------------------------
    def trace_scope(self, name: str, category=Category.COMPUTE, args: dict | None = None, cpu: bool = False) -> "Span":
        return Span(self, name, category, args, cpu=cpu)

    @contextlib.asynccontextmanager
    async def atrace_scope(self, name: str, category=Category.COMPUTE, args: dict | None = None):
        # no TraceMe: a coroutine's span stays open across awaits and
        # overlaps its siblings on the loop's thread, which is not what a
        # TraceMe (one thread's nested activity) records
        with Span(self, name, category, args, annotate=False):
            yield

    def instant(self, name: str, category=Category.INSTR, args: dict | None = None) -> None:
        """Point event: a zero-length TraceMe and a record entry."""
        ann = _annotation(name, args)
        if ann is not None:
            ann.__enter__()
            ann.__exit__(None, None, None)
        t = time.monotonic_ns()
        self._events.append((name, t, t, threading.get_ident(), args, category, "i"))

    def add_span(self, name: str, start_ns: int, end_ns: int, category=Category.COMPUTE, args: dict | None = None) -> None:
        """A span that has already ended, timed by someone else (the jax
        monitoring listener, ``utils/compile_cache.py``), on the calling
        thread. Record only: its TraceMe's time has passed."""
        self._events.append((name, start_ns, end_ns, threading.get_ident(), args, category, "X"))

    def _raw(self) -> list[tuple]:
        while True:
            try:
                return list(self._events)
            except RuntimeError:  # another thread appended during the copy
                continue

    def record(self) -> SpanRecord:
        return SpanRecord(
            PROCESS_START_NS,
            [RecordEntry(*e) for e in self._raw()],
            {t.ident: t.name for t in threading.enumerate()},
        )

    # -- persistence -------------------------------------------------------
    def chrome_events(self) -> list[dict[str, Any]]:
        """The record as Chrome trace events ("X" and "i"), built here and
        not on the hot path."""
        out = []
        for name, t0, t1, tid, args, category, ph in self._raw():
            ev = {
                "name": name,
                "ph": ph,
                "pid": self._pid,
                "tid": tid % 2**31,
                "ts": t0 / 1e3,
                "cat": category.value if isinstance(category, Category) else (category or "instr"),
            }
            if ph == "i":
                ev["s"] = "t"
            else:
                ev["dur"] = (t1 - t0) / 1e3
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def _path(self) -> str:
        out = self.config.output_dir or "/tmp/areal_tpu/traces"
        os.makedirs(out, exist_ok=True)
        role = f"{self.role}_" if self.role else ""
        return os.path.join(out, f"trace_{role}rank{self.rank}.json")

    def save(self, step: int | None = None, force: bool = False) -> None:
        if not self.enabled:
            return
        if not force and step is not None:
            if step - self._last_save_step < max(1, self.config.save_freq_steps):
                return
            self._last_save_step = step
        with open(self._path(), "w") as f:
            json.dump({"traceEvents": self.chrome_events(), "displayTimeUnit": "ms"}, f)

    def clear(self) -> None:
        self._events.clear()


def _annotation(name: str, args: dict | None):
    """A ``jax.profiler.TraceAnnotation`` for one span or event, or None in a
    process that has not imported jax: a span must never be what imports it
    (trainer classes drive remote workers and stay off jax). Values become
    the event's stats; ``,`` and ``#`` delimit TraceMe metadata and are
    dropped from strings."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)
    if cls is None:
        return None
    return cls(name, **_stats(args)) if args else cls(name)


def _stats(args: dict) -> dict:
    return {
        k: v if isinstance(v, (bool, int, float)) else str(v).replace(",", ";").replace("#", "")
        for k, v in args.items()
    }


class Span:
    """One span of the program: name, start, end, args, and the calling
    context's ``x-areal-trace`` ids as the shared identifier.

    Entering it always enters a TraceMe (``_annotation``), so the span lands
    in a running profiler session on the device trace's clock; leaving it
    always appends one tuple to the tracer's record. ``set`` adds args that
    are known only at the end (a pass's credited tokens). ``cpu`` adds the
    arg ``cpu_us``, the calling thread's CPU time over the span: a span that
    waited can then be told from one that worked."""

    __slots__ = ("_tracer", "_ann", "_cpu0", "name", "category", "args", "start_ns", "end_ns")

    def __init__(self, tracer: PerfTracer, name: str, category=Category.COMPUTE, args: dict | None = None, annotate: bool = True, cpu: bool = False):
        self._tracer = tracer
        self.name = name
        self.category = category
        task, session = _task_id_var.get(), _session_id_var.get()
        if task or session:
            args = dict(args or {})
            if task:
                args["task_id"] = task
            # session ids are the cross-process join key: merge_traces
            # output correlates trainer/controller/server spans on them
            if session:
                args["session_id"] = session
        self.args = args
        self._ann = _annotation(name, args) if annotate else None
        self._cpu0: int | None = 0 if cpu else None
        self.start_ns = self.end_ns = 0

    def set(self, **args: Any) -> None:
        self.args = {**(self.args or {}), **args}
        if self._ann is not None:
            self._ann.set_metadata(**_stats(args))

    def __enter__(self) -> "Span":
        # the CPU clock is read outside the TraceMe and the record's times, so
        # that both hold the same span to within a microsecond or two
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time_ns()
        if self._ann is not None:
            self._ann.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.monotonic_ns()
        if self._cpu0 is not None:
            self.set(cpu_us=(time.thread_time_ns() - self._cpu0) // 1000)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._events.append(
            (self.name, self.start_ns, self.end_ns, threading.get_ident(), self.args, self.category, "X")
        )


_gc_span: Span | None = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: a generation-2 collection is the span ``areal.gc`` on
    the thread it ran on (it holds every thread up); nothing for the young
    generations, which take microseconds and run thousands of times."""
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span = Span(_TRACER, "areal.gc", Category.INSTR).__enter__()
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.set(collected=info["collected"], uncollectable=info["uncollectable"])
        span.__exit__(None, None, None)


class SlowSpanWatch:
    """The program's own reader of the record, for an operator: told every
    span of one name as it ends, it writes ONE WARNING line for a span that
    took over ``factor`` x the median of the last ``window`` before it, with
    what the record holds of that time: the span's args (``cpu_us``: did it
    work or wait), its children's self times, and every entry of any thread
    that overlaps it (a collection, a program built, request events, a weight
    update). No counter and no gauge: the line is the whole of it."""

    def __init__(self, name: str, window: int = 64, factor: float = 3.0, min_samples: int = 8, floor_ms: float = 50.0):
        self.name = name
        self.factor = factor
        self.min_samples = min_samples
        # a span under the floor is never reported: at toy sizes a pass of
        # 9 ms among passes of 3 is the host's noise and nobody's stall
        self.floor_ns = int(floor_ms * 1e6)
        self._durs: deque[int] = deque(maxlen=window)

    def observe(self, span: Span) -> str | None:
        """The warning line where ``span`` (ended) was slow, else None."""
        dur = span.end_ns - span.start_ns
        durs = self._durs
        slow = None
        if len(durs) >= self.min_samples:
            med = statistics.median(durs)
            if dur > self.factor * med and dur >= self.floor_ns:
                slow = self._line(span, dur, med)
                logger.warning(slow)
        durs.append(dur)
        return slow

    def _line(self, span: Span, dur: int, med: float) -> str:
        t0, t1, me = span.start_ns, span.end_ns, threading.get_ident()
        inside, beside = [], []
        for e in reversed(span._tracer._raw()):
            if e[2] < t0:  # entries lie in the order they ended
                break
            if e[1] > t1 or (e[0] == span.name and e[1] == t0 and e[3] == me):
                continue
            (inside if e[3] == me and e[1] >= t0 else beside).append(e)
        ms = lambda ns: f"{ns / 1e6:.3f}"  # noqa: E731
        phases = ", ".join(f"{k.removeprefix('areal.')} {ms(v)}" for k, v in sorted(self_times(inside).items(), key=lambda kv: -kv[1]))
        by: dict[str, list] = {}
        for e in beside:
            by.setdefault(e[0], []).append(e)
        others = []
        for name, es in sorted(by.items(), key=lambda kv: -max(e[2] - e[1] for e in kv[1])):
            if len(es) > 3:  # the requests in flight: a count and the longest
                others.append(f"{name} x {len(es)} (longest {ms(max(e[2] - e[1] for e in es))} ms)")
            else:
                others += [f"{name} {ms(e[2] - e[1])} ms at {(e[1] - t0) / 1e6:+.3f} (thread {e[3]}) {e[4] or ''}".rstrip() for e in es]
        return (
            f"slow {self.name}: {ms(dur)} ms, {dur / med:.1f} x the median {ms(med)} ms of the last {len(self._durs)}; "
            f"{span.args or {}}; self ms by phase: {phases or 'no child span'}; "
            f"{len(beside)} overlapping entries of other spans and threads: {'; '.join(others[:16]) or 'none'}"
        )


def self_times(entries) -> dict[str, int]:
    """ns by name of one thread's nested spans, each less what its children
    cover (``entries``: record tuples or ``RecordEntry``s of ONE thread)."""
    out: dict[str, int] = {}
    stack: list[tuple[str, int]] = []  # (name, end) of the spans open at the sweep's instant
    for e in sorted((e for e in entries if e[6] == "X"), key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= e[1]:
            stack.pop()
        dur = e[2] - e[1]
        out[e[0]] = out.get(e[0], 0) + dur
        if stack:
            out[stack[-1][0]] -= dur
        stack.append((e[0], e[2]))
    return out


@dataclass
class SessionRecord:
    """Lifecycle of one rollout episode (reference SessionTracer :920-1125)."""

    session_id: str
    start_ts: float = field(default_factory=time.time)
    phases: list[dict[str, Any]] = field(default_factory=list)
    status: str | None = None  # accepted | rejected
    end_ts: float | None = None


class SessionTracer:
    def __init__(
        self,
        output_dir: str | None = None,
        enabled: bool = True,
        flush_threshold: int = 1,
    ):
        self.enabled = enabled
        self.output_dir = output_dir or "/tmp/areal_tpu/traces"
        # finalized records buffer until this many are ready (reference
        # SessionTracerConfig.flush_threshold); <=0 falls back to 1
        self.flush_threshold = max(1, flush_threshold)
        self._records: dict[str, SessionRecord] = {}
        self._done: list[dict] = []
        self._lock = threading.Lock()

    def start_session(self, session_id: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._records[session_id] = SessionRecord(session_id)
        _session_id_var.set(session_id)

    @contextlib.contextmanager
    def phase(self, name: str, session_id: str | None = None):
        sid = session_id or _session_id_var.get()
        t0 = time.time()
        try:
            yield
        finally:
            if self.enabled and sid is not None:
                with self._lock:
                    rec = self._records.get(sid)
                    if rec is not None:
                        rec.phases.append(
                            {"name": name, "start": t0, "dur": time.time() - t0}
                        )

    def finalize(self, session_id: str, status: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            rec = self._records.pop(session_id, None)
            if rec is None:
                return
            rec.status = status
            rec.end_ts = time.time()
            self._done.append(
                {
                    "session_id": rec.session_id,
                    "start": rec.start_ts,
                    "end": rec.end_ts,
                    "status": rec.status,
                    "phases": rec.phases,
                }
            )
            ready = len(self._done) >= self.flush_threshold
        if ready:
            self.flush()

    def flush(self) -> None:
        """Write buffered finalized records to sessions.jsonl."""
        with self._lock:
            done, self._done = self._done, []
        if not done:
            return
        os.makedirs(self.output_dir, exist_ok=True)
        path = os.path.join(self.output_dir, "sessions.jsonl")
        with open(path, "a") as f:
            for d in done:
                f.write(json.dumps(d) + "\n")


# ---------------------------------------------------------------------------
# module-level default tracer (reference module functions :1858-1940)
# ---------------------------------------------------------------------------

_TRACER = PerfTracer(PerfTracerConfig(enabled=False))
_SESSIONS = SessionTracer(enabled=False)


gc.callbacks.append(_on_gc)


def configure(config: PerfTracerConfig, rank: int = 0, role: str | None = None) -> None:
    global _TRACER, _SESSIONS
    # the new tracer takes the record over: what ran before the trainer
    # configured its tracer (imports, engine set-up) stays readable
    _TRACER = PerfTracer(config, rank=rank, role=role, events=_TRACER._events)
    # session tracing follows its own sub-config when given (reference
    # SessionTracerConfig), else the perf tracer's enabled flag with
    # per-record writes (the pre-knob behavior)
    sess = getattr(config, "session_tracer", None)
    _SESSIONS = SessionTracer(
        config.output_dir,
        enabled=sess.enabled if sess is not None else config.enabled,
        flush_threshold=sess.flush_threshold if sess is not None else 1,
    )


# on-demand device profiling state: one jax.profiler trace at a time per
# process (the profiler itself is a process-global); each capture gets its
# own timestamped dir so postmortem can link individual sessions
_PROFILE_LOCK = threading.Lock()
_PROFILE_DIR: str | None = None


def default_profile_root(output_dir: str | None = None) -> str:
    return os.path.join(
        output_dir or _TRACER.config.output_dir or "/tmp/areal_tpu/traces",
        "xprof",
    )


def start_device_profile(output_dir: str | None = None) -> str:
    """Begin a detailed XLA device profile (jax.profiler trace; view in
    TensorBoard/XProf). Returns the capture dir. Raises RuntimeError when
    a profile is already running (one at a time per process — the HTTP
    endpoint turns this into a 409). Reference knob:
    PerfTracerConfig.profile_steps."""
    global _PROFILE_DIR
    import jax

    with _PROFILE_LOCK:
        if _PROFILE_DIR is not None:
            raise RuntimeError(
                f"device profile already active at {_PROFILE_DIR}"
            )
        d = os.path.join(
            default_profile_root(output_dir),
            f"profile_{int(time.time() * 1000)}",
        )
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        _PROFILE_DIR = d
    return d


def stop_device_profile(only_dir: str | None = None) -> str | None:
    """End the active capture; returns its dir (None if none active).
    ``only_dir`` stops the capture only if it is still the active one —
    the guard profile_for's background timer needs so a stale timer from
    an early-stopped capture can never truncate a newer unrelated one."""
    global _PROFILE_DIR
    import jax

    with _PROFILE_LOCK:
        if _PROFILE_DIR is None:
            return None
        if only_dir is not None and _PROFILE_DIR != only_dir:
            return None
        try:
            jax.profiler.stop_trace()
        finally:
            d, _PROFILE_DIR = _PROFILE_DIR, None
    return d


def device_profile_active() -> str | None:
    """The active capture's dir, or None."""
    with _PROFILE_LOCK:
        return _PROFILE_DIR


def profile_for(duration_s: float, output_dir: str | None = None) -> str:
    """Start a capture and stop it after ``duration_s`` on a background
    timer thread — the POST /debug/profile implementation. Returns the
    capture dir immediately; the xplane/trace files land at stop time."""
    d = start_device_profile(output_dir)

    def _stop():
        time.sleep(max(0.0, duration_s))
        try:
            stop_device_profile(only_dir=d)
        except Exception:  # noqa: BLE001 — a failed stop must not kill
            # the timer thread silently holding the active slot
            logger.exception("device-profile stop failed")

    threading.Thread(
        target=_stop, name="device-profile-stop", daemon=True
    ).start()
    return d


def get_tracer() -> PerfTracer:
    return _TRACER


def get_session_tracer() -> SessionTracer:
    return _SESSIONS


def trace_scope(name: str, category=Category.COMPUTE, args: dict | None = None, cpu: bool = False):
    return Span(_TRACER, name, category, args, cpu=cpu)


def atrace_scope(name: str, category=Category.COMPUTE, args: dict | None = None):
    return _TRACER.atrace_scope(name, category, args)


def instant(name: str, category=Category.INSTR, args: dict | None = None) -> None:
    _TRACER.instant(name, category, args)


def save(step: int | None = None, force: bool = False) -> None:
    _TRACER.save(step=step, force=force)
    _SESSIONS.flush()  # buffered session records ride the same cadence


def merge_traces(paths: list[str], out_path: str) -> None:
    """Merge per-rank trace files into one (reference
    tools/perf_trace_converter.py role). pids are remapped per source file so
    ranks appear as separate process tracks."""
    merged: list[dict[str, Any]] = []
    for i, p in enumerate(paths):
        with open(p) as f:
            data = json.load(f)
        for ev in data.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = i
            merged.append(ev)
        merged.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": i,
                "args": {"name": os.path.basename(p)},
            }
        )
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
