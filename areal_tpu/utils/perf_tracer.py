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
spans on the same clock as the device's ops. The Chrome event is written only
while the ``PerfTracer`` is enabled. A process that has not imported jax emits
no annotation and is not made to import it.

Surface:
    configure(cfg, rank=..., role=...)      process-level setup
    trace_scope(name, category=..., args=)  sync context manager (``Span``)
    atrace_scope(name, ...)                 async context manager
    instant(name, ...)                      point event
    counter(name, **values)                 counter track
    trace_perf(name, category=...)          decorator
    save(step=..., force=...)               periodic/final flush
    SessionTracer / trace_session("phase")  rollout lifecycle records
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Any

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


class PerfTracer:
    """Catapult JSON event collector for one process."""

    def __init__(self, config: PerfTracerConfig, rank: int = 0, role: str | None = None):
        self.config = config
        self.enabled = config.enabled
        self.rank = rank
        self.role = role
        self._events: list[dict[str, Any]] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._last_save_step = -1

    # -- event emission ----------------------------------------------------
    def _ts_us(self) -> float:
        return time.perf_counter_ns() / 1e3

    def _base(self, name: str, ph: str, category) -> dict[str, Any]:
        cat = category.value if isinstance(category, Category) else (category or "instr")
        return {
            "name": name,
            "ph": ph,
            "pid": self._pid,
            "tid": threading.get_ident() % 2**31,
            "ts": self._ts_us(),
            "cat": cat,
        }

    def _push(self, ev: dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)
            # bound memory on long runs: keep the newest max_events
            cap = getattr(self.config, "max_events", 200_000)
            if cap and len(self._events) > cap:
                del self._events[: len(self._events) - cap]

    def trace_scope(self, name: str, category=Category.COMPUTE, args: dict | None = None) -> "Span":
        return Span(self, name, category, args)

    @contextlib.asynccontextmanager
    async def atrace_scope(self, name: str, category=Category.COMPUTE, args: dict | None = None):
        # Chrome event only: a coroutine's span stays open across awaits and
        # overlaps its siblings on the loop's thread, which is not what a
        # TraceMe (one thread's nested activity) records
        with Span(self, name, category, args, annotate=False):
            yield

    def instant(self, name: str, category=Category.INSTR, args: dict | None = None) -> None:
        """Point event: a zero-length TraceMe, and a Chrome "i" event while
        the tracer is enabled."""
        ann = _annotation(name, args)
        if ann is not None:
            ann.__enter__()
            ann.__exit__(None, None, None)
        if not self.enabled:
            return
        ev = self._base(name, "i", category)
        ev["s"] = "t"
        if args:
            ev["args"] = args
        self._push(ev)

    def counter(self, name: str, **values: float) -> None:
        if not self.enabled:
            return
        ev = self._base(name, "C", Category.INSTR)
        ev["args"] = values
        self._push(ev)

    # -- persistence -------------------------------------------------------
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
        with self._lock:
            events = list(self._events)
        with open(self._path(), "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)

    def clear(self) -> None:
        with self._lock:
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
    in a running profiler session on the device trace's clock; the Chrome
    "X" event is written only while the tracer is enabled. ``set`` adds args
    that are known only at the end (a pass's credited tokens)."""

    __slots__ = ("_tracer", "_ann", "_t0", "name", "category", "args")

    def __init__(self, tracer: PerfTracer, name: str, category=Category.COMPUTE, args: dict | None = None, annotate: bool = True):
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
        self._t0: float | None = None

    def set(self, **args: Any) -> None:
        self.args = {**(self.args or {}), **args}
        if self._ann is not None:
            self._ann.set_metadata(**_stats(args))

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._tracer.enabled:
            self._t0 = self._tracer._ts_us()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._t0 is not None:
            tr = self._tracer
            ev = tr._base(self.name, "X", self.category)
            ev["ts"] = self._t0
            ev["dur"] = tr._ts_us() - self._t0
            if self.args:
                ev["args"] = self.args
            tr._push(ev)


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


def configure(config: PerfTracerConfig, rank: int = 0, role: str | None = None) -> None:
    global _TRACER, _SESSIONS
    _TRACER = PerfTracer(config, rank=rank, role=role)
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


def trace_scope(name: str, category=Category.COMPUTE, args: dict | None = None):
    return _TRACER.trace_scope(name, category, args)


def atrace_scope(name: str, category=Category.COMPUTE, args: dict | None = None):
    return _TRACER.atrace_scope(name, category, args)


def instant(name: str, category=Category.INSTR, args: dict | None = None) -> None:
    _TRACER.instant(name, category, args)


def counter(name: str, **values: float) -> None:
    _TRACER.counter(name, **values)


def save(step: int | None = None, force: bool = False) -> None:
    _TRACER.save(step=step, force=force)
    _SESSIONS.flush()  # buffered session records ride the same cadence


def trace_perf(name: str, category=Category.COMPUTE):
    """Decorator tracing every call of a function (sync or async)."""

    def deco(fn):
        if _is_coroutine_fn(fn):

            @functools.wraps(fn)
            async def awrapper(*a, **kw):
                with _TRACER.trace_scope(name, category):
                    return await fn(*a, **kw)

            return awrapper

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with _TRACER.trace_scope(name, category):
                return fn(*a, **kw)

        return wrapper

    return deco


def trace_session(phase_name: str):
    """Decorator recording a session phase (reference @trace_session use in
    workflow/rlvr.py:77,124)."""

    def deco(fn):
        if _is_coroutine_fn(fn):

            @functools.wraps(fn)
            async def awrapper(*a, **kw):
                with _SESSIONS.phase(phase_name):
                    return await fn(*a, **kw)

            return awrapper

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with _SESSIONS.phase(phase_name):
                return fn(*a, **kw)

        return wrapper

    return deco


def _is_coroutine_fn(fn) -> bool:
    import asyncio

    return asyncio.iscoroutinefunction(fn)


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
