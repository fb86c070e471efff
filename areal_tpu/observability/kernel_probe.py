"""Kernel-grade decode observatory: per-step phase attribution + roofline.

The trainer got its step observatory in PRs 7/9 (``step_timeline``); this is
the serving-side twin at kernel granularity. Every pass of the decode loop
that does real work becomes ONE :class:`DecodeStepTimeline` obeying the same
exact-sum identity contract: named phases plus an explicit ``other_s``
residual sum EXACTLY to the step's wall time. "The loop spends its time in
X" is then an assertion about measured accumulators, never a vibe.

Phase vocabulary (docs/perf.md "Kernel observatory"):

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

Costs come from the compiled executable itself: :class:`ProbedFn` wraps each
jitted decode/prefill function, obtains the executable via
``fn.lower(*args).compile()`` (ahead-of-time — the SAME compile the first
call would have paid, not a second one), and records
``compiled.cost_analysis()`` FLOPs/bytes. Backends that return nothing
(CPU, some runtimes) fall back to the analytic model in ``hw_accounting``.
Joined against the chip peak table (or a one-time measured host calibration
when the chip is unknown) this yields the per-step achieved-roofline
fraction: achieved FLOPs/s over ``min(peak_flops, intensity * peak_membw)``.

Catalogued metrics: ``areal_decode_phase_seconds{phase}``,
``areal_decode_step_flops``, ``areal_decode_roofline_fraction``; the live
summary is served under ``/statusz`` ``kernels`` and folded into bench.py
round payloads as ``detail.kernels``.

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
from typing import Any, Callable, Iterator

from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability import hw_accounting as hw
from areal_tpu.utils import perf_tracer

# canonical phase order (docs/perf.md "Kernel observatory"); breakdown()
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


# ---------------------------------------------------------------------------
# cost extraction + roofline math
# ---------------------------------------------------------------------------


def cost_from_analysis(ca: Any) -> tuple[float, float] | None:
    """Normalize ``compiled.cost_analysis()`` output to ``(flops, bytes)``.

    The API has returned a dict, a list of per-computation dicts, and None
    across jax versions/backends; anything without a positive ``flops``
    count means "the backend declined" and the caller falls back to the
    analytic model."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    try:
        flops = float(ca.get("flops") or 0.0)
        nbytes = float(ca.get("bytes accessed") or 0.0)
    except (TypeError, ValueError):
        return None
    if flops <= 0.0:
        return None
    return flops, nbytes


def roofline_fraction(
    flops: float,
    nbytes: float,
    elapsed_s: float,
    peak_flops: float | None,
    peak_membw: float | None,
    n_chips: int = 1,
) -> float | None:
    """Achieved/attainable fraction under the classic roofline:
    ``attainable = min(peak_flops, intensity * peak_membw)`` where
    intensity = flops/byte. None when the inputs can't support a number
    (no FLOP count, no peak, zero window) — never fabricated."""
    if flops <= 0.0 or elapsed_s <= 0.0 or not peak_flops:
        return None
    chips = max(1, int(n_chips))
    attainable = peak_flops * chips
    if nbytes > 0.0 and peak_membw:
        attainable = min(attainable, (flops / nbytes) * peak_membw * chips)
    if attainable <= 0.0:
        return None
    return min(1.0, (flops / elapsed_s) / attainable)


class ProbedFn:
    """Transparent wrapper around a jitted function that harvests
    ``cost_analysis`` from the ahead-of-time compile path.

    First call: ``fn.lower(*args).compile()`` — this IS the compile the
    first jit call would have triggered (the persistent compilation cache
    still applies), so the probe adds no duplicate compilation. The
    compiled executable's FLOPs/bytes are recorded against ``key`` in the
    probe's cost registry (source ``device``), or the analytic estimate
    when the backend returns nothing (source ``analytic``). Subsequent
    calls invoke the cached executable directly; if its avals drift (a
    weight update changed a dtype/shape) the wrapper degrades permanently
    to the plain jit fn — correctness never depends on the probe."""

    __slots__ = ("_fn", "_probe", "_key", "_analytic", "_compiled", "_plain")

    def __init__(
        self,
        fn: Callable,
        probe: "KernelProbe | None",
        key: tuple,
        analytic: tuple[float, float] | None = None,
    ):
        self._fn = fn
        self._probe = probe
        self._key = key
        self._analytic = analytic
        self._compiled: Callable | None = None
        self._plain = probe is None

    def _compile(self, args) -> Callable | None:
        try:
            compiled = self._fn.lower(*args).compile()
        except Exception:  # noqa: BLE001 — backends without AOT: plain jit
            self._plain = True
            if self._probe is not None and self._analytic is not None:
                self._probe.record_cost(self._key, *self._analytic, "analytic")
            return None
        cost = None
        try:
            cost = cost_from_analysis(compiled.cost_analysis())
        except Exception:  # noqa: BLE001 — cost_analysis may raise outright
            cost = None
        if self._probe is not None:
            if cost is not None:
                self._probe.record_cost(self._key, cost[0], cost[1], "device")
            elif self._analytic is not None:
                self._probe.record_cost(self._key, *self._analytic, "analytic")
        return compiled

    def lower(self, *args, **kwargs):
        """AOT passthrough: the engine's precompile() warms programs via
        ``fn.lower(shapes).compile()`` — delegate so the wrapper is a
        drop-in for the plain jit fn (the warm compile lands in the
        persistent cache, making this wrapper's own AOT compile a replay)."""
        return self._fn.lower(*args, **kwargs)

    def __call__(self, *args):
        if self._plain:
            return self._fn(*args)
        if self._compiled is None:
            self._compiled = self._compile(args)
            if self._compiled is None:
                return self._fn(*args)
        try:
            return self._compiled(*args)
        except (TypeError, ValueError):
            # aval drift (e.g. params swapped for a different dtype after a
            # weight update): the AOT executable is stale — degrade to the
            # plain jit fn for good, it retraces as needed
            self._compiled = None
            self._plain = True
            return self._fn(*args)


class KernelProbe:
    """Per-engine kernel observatory: step timelines + cost registry +
    roofline attribution.

    The decode loop opens one timeline per pass (``begin_step``), abandons
    idle/paused/held passes, and completes productive ones with the
    fn-cache key of the chunk it DRAINED this pass (steady state drains
    exactly one chunk per pass, so per-step FLOPs are the drained chunk's
    cost). Construction is init-time only: peak resolution may calibrate
    the host backend with real device work, which is why it must never
    run on the hot path."""

    def __init__(
        self,
        model_cfg=None,
        n_chips: int = 1,
        device: Any | None = None,
        max_recent: int = DEFAULT_RECENT_STEPS,
        calibrate: bool = True,
        peak_flops: float | None = None,
        peak_membw: float | None = None,
    ):
        self.model_cfg = model_cfg
        self.n_chips = max(1, int(n_chips))
        self._obs = obs_catalog.kernel_metrics()
        self._lock = threading.Lock()
        self._costs: dict[tuple, dict[str, Any]] = {}
        self._recent: deque[dict] = deque(maxlen=max_recent)
        self._started = 0
        self._completed = 0
        self._abandoned = 0
        self._phase_sums: dict[str, float] = {p: 0.0 for p in DECODE_PHASES}
        self._other_sum = 0.0
        self._total_sum = 0.0
        self._tokens_sum = 0.0
        self._flops_sum = 0.0
        self._roofline_sum = 0.0
        self._roofline_n = 0
        if peak_flops is not None:
            self.peak_flops, self.peak_membw = peak_flops, peak_membw
            self.peak_source = "override"
        elif calibrate:
            # a TPU resolves from the chip table or raises; the CPU backend
            # measures the host once so the roofline fraction is still a
            # real number (init-time only: device work)
            self.peak_flops, self.peak_membw, self.peak_source = (
                hw.resolve_chip_peaks(device)
            )
        else:
            self.peak_flops = hw.chip_peak_flops(device)
            self.peak_membw = hw.chip_peak_membw(device)
            self.peak_source = "unknown" if self.peak_flops is None else "spec"

    # -- cost registry -----------------------------------------------------

    def record_cost(
        self, key: tuple, flops: float, nbytes: float, source: str
    ) -> None:
        with self._lock:
            self._costs[key] = {
                "flops": float(flops),
                "bytes": float(nbytes),
                "source": source,
            }

    def cost_for(self, key: tuple | None) -> dict[str, Any] | None:
        if key is None:
            return None
        with self._lock:
            return self._costs.get(key)

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
        self,
        tl: DecodeStepTimeline,
        tokens: int = 0,
        cost_key: tuple | None = None,
    ) -> dict[str, float]:
        """Close a productive pass. ``cost_key`` is the fn-cache key of
        the chunk drained this pass; its registered cost supplies the
        step's FLOPs/bytes for the roofline join."""
        bd = tl.breakdown()
        cost = self.cost_for(cost_key)
        flops = cost["flops"] if cost else 0.0
        nbytes = cost["bytes"] if cost else 0.0
        frac = roofline_fraction(
            flops,
            nbytes,
            bd["total_s"],
            self.peak_flops,
            self.peak_membw,
            self.n_chips,
        )
        for p in tl.phases:
            self._obs.phase_seconds.labels(phase=p).observe(bd[f"{p}_s"])
        self._obs.phase_seconds.labels(phase="other").observe(bd["other_s"])
        if flops > 0.0:
            self._obs.step_flops.set(flops)
        if frac is not None:
            self._obs.roofline_fraction.set(frac)
            bd["roofline_fraction"] = frac
        with self._lock:
            self._completed += 1
            for p, v in tl.phases.items():
                self._phase_sums[p] = self._phase_sums.get(p, 0.0) + v
            self._other_sum += bd["other_s"]
            self._total_sum += bd["total_s"]
            self._tokens_sum += max(0, int(tokens))
            self._flops_sum += flops
            if frac is not None:
                self._roofline_sum += frac
                self._roofline_n += 1
            self._recent.append(
                {
                    "breakdown": bd,
                    "tokens": int(tokens),
                    "flops": flops,
                    "bytes": nbytes,
                    "cost_source": cost["source"] if cost else None,
                }
            )
        return bd

    # -- summaries ---------------------------------------------------------

    def recent(self, n: int | None = None) -> list[dict]:
        with self._lock:
            out = list(self._recent)
        if n is None:
            return out
        return out[-n:] if n > 0 else []

    def stats(self) -> dict[str, Any]:
        """Steady-state summary for /statusz ``kernels`` and bench
        ``detail.kernels``: per-phase mean seconds (dominant phase named),
        mean roofline fraction, cost registry, peak provenance."""
        with self._lock:
            n = self._completed
            phase_means = {
                p: (self._phase_sums.get(p, 0.0) / n if n else 0.0)
                for p in DECODE_PHASES
            }
            other_mean = self._other_sum / n if n else 0.0
            total_mean = self._total_sum / n if n else 0.0
            roofline_mean = (
                self._roofline_sum / self._roofline_n
                if self._roofline_n
                else None
            )
            tok_s = (
                self._tokens_sum / self._total_sum if self._total_sum else 0.0
            )
            costs = {
                "|".join(str(k) for k in key): dict(v)
                for key, v in self._costs.items()
            }
            flops_sum = self._flops_sum
            started, abandoned = self._started, self._abandoned
        dominant = None
        if n:
            spans = dict(phase_means)
            spans["other"] = other_mean
            dominant = max(spans, key=spans.get)
        out: dict[str, Any] = {
            "steps": n,
            "started": started,
            "abandoned": abandoned,
            "phase_means_s": phase_means,
            "other_mean_s": other_mean,
            "total_mean_s": total_mean,
            "dominant_phase": dominant,
            "roofline_fraction": roofline_mean,
            "tok_s": tok_s,
            "flops_total": flops_sum,
            "peaks": {
                "flops": self.peak_flops,
                "membw": self.peak_membw,
                "source": self.peak_source,
                "n_chips": self.n_chips,
            },
            "costs": costs,
        }
        return out
