"""Decode-step observatory: per-pass phase attribution of the engine loop.

The trainer got its step observatory in PRs 7/9 (``step_timeline``); this is
the serving-side twin. Every pass of the decode loop that does real work
becomes ONE :class:`DecodeStepTimeline` obeying the same exact-sum identity
contract: named phases plus an explicit ``other_s`` residual sum EXACTLY to
the step's wall time. "The loop spends its time in X" is then an assertion
about measured accumulators, never a vibe.

Phase vocabulary (docs/observability.md "Decode-step phases"):

    admission     lifecycle reaping, queue pops, slot updates, dup admits
    radix_match   prefix-cache lookups for newly admitted primaries
    prefill       prompt prefill jit calls (cold + suffix/prefixed)
    dispatch      building + launching the fused decode-chunk jit
    device_wait   blocking host pull of the PREVIOUS chunk's packed output
    bookkeeping   per-token credit: stop checks, streaming, stats

All of them are HOST spans on the decode thread — the loop dispatches chunk
N and only then drains chunk N-1, so the device executes behind the host and
the *visible* device time is exactly ``device_wait``. Every phase goes through
``perf_tracer.trace_scope`` as ``areal.decode.<phase>`` (and each productive
pass as the parent span ``areal.decode.pass``), so a device profile shows them
on the device trace's clock, beside the ops of the fused chunk, which carry the
model's ``jax.named_scope`` names (docs/observability.md "Spans and scopes").

What a program costs and what share of the chip's roofline it reaches is read
from a device trace against ``benchmarks/chip/benchlib/peaks.py`` (PERF.md
section 3), never from these host spans.

Catalogued metric: ``areal_decode_phase_seconds{phase}``; the live summary is
served under ``/statusz`` ``kernels``.

Overhead discipline: phase marks are two ``time.monotonic()`` reads and a
dict add; nothing here syncs the device, pulls an array, or coerces a
device value on the hot path (the repo-wide PRF lint is the acceptance
check for that).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Iterator

from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.utils import perf_tracer

# canonical phase order (docs/observability.md "Decode-step phases"); breakdown()
# also carries any ad-hoc phase a caller added, so the identity never
# silently drops one
DECODE_PHASES = (
    "admission",
    "radix_match",
    "prefill",
    "draft",
    "dispatch",
    "device_wait",
    "verify",
    "bookkeeping",
)

# completed step breakdowns retained for self-tests / statusz scrapes
DEFAULT_RECENT_STEPS = 64


class DecodeStepTimeline:
    """Phase accumulator for ONE productive pass of the decode loop.

    Unlike the trainer's :class:`~.step_timeline.StepTimeline` (outer phase
    wins, inner contributions suppressed), decode phases nest
    *exclusively*: entering an inner phase PAUSES the enclosing one, so
    ``radix_match`` inside ``admission`` and ``prefill`` inside the admit
    path each own their own span and the named sum still can never exceed
    the wall clock. All marks are ``time.monotonic()`` reads on the decode
    thread — no device sync, no host pulls. Each phase is also one
    ``areal.decode.<phase>`` span (``perf_tracer.trace_scope``); the spans
    nest by time, an enclosing phase's span contains its inner ones.
    """

    __slots__ = ("started_ts", "phases", "_stack", "_t0")

    def __init__(self) -> None:
        self.started_ts = time.monotonic()
        self.phases: dict[str, float] = {p: 0.0 for p in DECODE_PHASES}
        self._stack: list[str] = []  # open phase names, innermost last
        self._t0 = 0.0  # start of the current exclusive span

    def add(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + max(0.0, seconds)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        now = time.monotonic()
        if self._stack:
            # pause the enclosing phase: credit its span so far, then let
            # the inner phase own the clock until it exits
            self.add(self._stack[-1], now - self._t0)
        self._stack.append(name)
        self._t0 = now
        try:
            with perf_tracer.trace_scope("areal.decode." + name):
                yield
        finally:
            now = time.monotonic()
            self.add(name, now - self._t0)
            self._stack.pop()
            self._t0 = now  # the enclosing phase resumes here

    def breakdown(self, end_ts: float | None = None) -> dict[str, float]:
        """Per-phase durations + ``other_s`` residual + ``total_s``.

        Identity contract (PRs 7/9): ``sum(<phase>_s) + other_s ==
        total_s`` exactly. Spans are exclusive on one thread, so the only
        way the named sum can exceed the wall clock is sub-microsecond
        float noise — ``total_s`` absorbs it instead of clamping a phase."""
        end = end_ts if end_ts is not None else time.monotonic()
        named = sum(self.phases.values())
        total = max(0.0, end - self.started_ts, named)
        bd: dict[str, float] = {f"{p}_s": v for p, v in self.phases.items()}
        bd["other_s"] = total - named
        bd["total_s"] = total
        return bd


class KernelProbe:
    """Per-engine decode-step observatory: step timelines and their sums.

    The decode loop opens one timeline per pass (``begin_step``), abandons
    idle/paused/held passes, and completes productive ones with the tokens
    the pass credited."""

    def __init__(self, max_recent: int = DEFAULT_RECENT_STEPS):
        self._obs = obs_catalog.kernel_metrics()
        self._lock = threading.Lock()
        self._recent: deque[dict] = deque(maxlen=max_recent)
        self._started = 0
        self._completed = 0
        self._abandoned = 0
        self._phase_sums: dict[str, float] = {p: 0.0 for p in DECODE_PHASES}
        self._other_sum = 0.0
        self._total_sum = 0.0

    # -- step lifecycle ----------------------------------------------------

    def begin_step(self) -> DecodeStepTimeline:
        with self._lock:
            self._started += 1
        return DecodeStepTimeline()

    def abandon_step(self, tl: DecodeStepTimeline) -> None:
        """Discard a pass that did no chunk work (idle poll, paused,
        hold-fence window, cache torn down): no metrics, no identity
        record — recorded steps are always real steps."""
        with self._lock:
            self._abandoned += 1

    def complete_step(
        self, tl: DecodeStepTimeline, tokens: int = 0
    ) -> dict[str, float]:
        """Close a productive pass that credited ``tokens``."""
        bd = tl.breakdown()
        for p in tl.phases:
            self._obs.phase_seconds.labels(phase=p).observe(bd[f"{p}_s"])
        self._obs.phase_seconds.labels(phase="other").observe(bd["other_s"])
        with self._lock:
            self._completed += 1
            for p, v in tl.phases.items():
                self._phase_sums[p] = self._phase_sums.get(p, 0.0) + v
            self._other_sum += bd["other_s"]
            self._total_sum += bd["total_s"]
            self._recent.append({"breakdown": bd, "tokens": int(tokens)})
        return bd

    # -- summaries ---------------------------------------------------------

    def recent(self, n: int | None = None) -> list[dict]:
        with self._lock:
            out = list(self._recent)
        if n is None:
            return out
        return out[-n:] if n > 0 else []

    def stats(self) -> dict[str, Any]:
        """Steady-state summary for /statusz ``kernels``: per-phase mean
        seconds (dominant phase named) and step counts. (Tokens a second are
        not the probe's to guess: /statusz ``row_steps`` has the steps and
        the live row-steps the drains counted.)"""
        with self._lock:
            n = self._completed
            phase_means = {
                p: (self._phase_sums.get(p, 0.0) / n if n else 0.0)
                for p in DECODE_PHASES
            }
            other_mean = self._other_sum / n if n else 0.0
            total_mean = self._total_sum / n if n else 0.0
            started, abandoned = self._started, self._abandoned
        dominant = None
        if n:
            spans = dict(phase_means)
            spans["other"] = other_mean
            dominant = max(spans, key=spans.get)
        return {
            "steps": n,
            "started": started,
            "abandoned": abandoned,
            "phase_means_s": phase_means,
            "other_mean_s": other_mean,
            "total_mean_s": total_mean,
            "dominant_phase": dominant,
        }
