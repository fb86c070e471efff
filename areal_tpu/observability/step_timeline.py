"""Trainer step-phase timeline — the training-side twin of the request
timeline observatory.

The paper's core systems claim is that fully-async RL removes the trainer
bubble, yet the aggregate ``areal_train_step_seconds`` histogram cannot say
where a step's wall time went: blocking on rollout (the async bubble), host
batch prep, the fused fwd/bwd jit, the optimizer apply, the weight publish,
or checkpoint/eval I/O. :class:`StepTimeline` gives every global step the
same contract :class:`~areal_tpu.observability.timeline.RequestTimeline`
gives every request: named phases plus an explicit ``other_s`` residual that
sum EXACTLY to the step's wall time — "phases ≈ wall time" is then an
assertion that the residual is small, never an accounting identity that
hides gaps.

Phases (docs/observability.md "Trainer observatory"):

    rollout_wait       blocking in prepare_batch — THE async bubble
    host_prep          grid packing, device puts, advantage computation,
                       the step's stats after the update
    forward_backward   jitted device compute (fwd passes + fused fwd/bwd;
                       the single-microbatch fused path folds the optimizer
                       apply into this phase — see train_engine)
    optimizer          the separate grad-apply jit (multi-microbatch path)
    weight_publish     rollout pause + weight stream/commit + set_version
    ckpt_eval          saver/recover dumps + evaluation
    other_s            everything unattributed (stats export, logging, ...)

The trainer thread owns the timeline; the train engine contributes its
host_prep/forward_backward/optimizer spans through the thread-local
``engine_phase`` hook without any plumbing through call signatures.
Completed timelines feed the catalogued ``areal_train_phase_seconds{phase}``
histograms, the bubble-fraction / MFU / tok-s-per-chip gauges, and a
bounded ``recent()`` deque the self-tests and the per-step log line read.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Iterator

from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.utils import perf_tracer

# canonical phase order (docs/observability.md); breakdown() also carries
# any ad-hoc phase a caller added, so the identity never silently drops one
PHASES = (
    "rollout_wait",
    "host_prep",
    "forward_backward",
    "optimizer",
    "weight_publish",
    "ckpt_eval",
)

# completed step breakdowns retained for self-tests / statusz scrapes
DEFAULT_RECENT_STEPS = 64


class StepTimeline:
    """Phase accumulator for ONE global training step.

    Phases are duration accumulators, not timestamped events: one step
    re-enters ``host_prep``/``forward_backward`` once per microbatch, and
    only the per-phase totals are actionable. All accounting runs on the
    trainer thread, so phase spans never overlap and the named sums can
    never exceed the step wall time (beyond float noise, which
    ``breakdown`` absorbs to keep the identity exact).
    """

    __slots__ = ("step", "started_ts", "epoch_anchor", "phases", "_open_depth")

    def __init__(self, step: int):
        self.step = step
        self.started_ts = time.monotonic()
        self.epoch_anchor = time.time()
        self.phases: dict[str, float] = {p: 0.0 for p in PHASES}
        # open explicit-phase nesting depth: while a trainer-level phase is
        # open, engine_phase contributions are suppressed — the enclosing
        # span already owns that wall time, and double-attributing it
        # (e.g. eval forwards inside ckpt_eval) would push the named sum
        # past the wall clock and silently break the identity
        self._open_depth = 0

    def add(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + max(0.0, seconds)

    @contextlib.contextmanager
    def phase(self, name: str, category=perf_tracer.Category.COMPUTE, args: dict | None = None) -> Iterator[None]:
        """Accumulate the enclosed span under ``name``; it is also the span
        ``areal.train.<name>`` (``perf_tracer.trace_scope``)."""
        t0 = time.monotonic()
        self._open_depth += 1
        try:
            with perf_tracer.trace_scope("areal.train." + name, category, args):
                yield
        finally:
            self._open_depth -= 1
            self.add(name, time.monotonic() - t0)

    def breakdown(self, end_ts: float | None = None) -> dict[str, float]:
        """Per-phase durations + ``other_s`` residual + ``total_s``.

        Identity contract: ``sum(<phase>_s) + other_s == total_s`` exactly.
        Phases accumulate sequentially on one thread, so the only way the
        named sum can exceed the wall clock is sub-microsecond float noise
        — ``total_s`` absorbs it instead of clamping a phase."""
        end = end_ts if end_ts is not None else time.monotonic()
        named = sum(self.phases.values())
        total = max(0.0, end - self.started_ts, named)
        bd: dict[str, float] = {f"{p}_s": v for p, v in self.phases.items()}
        bd["other_s"] = total - named
        bd["total_s"] = total
        bd["bubble_fraction"] = (
            self.phases.get("rollout_wait", 0.0) / total if total > 0 else 0.0
        )
        return bd


# ---------------------------------------------------------------------------
# thread-local current timeline: the engine contributes phases to whatever
# step the OWNING trainer thread has open, with zero call-signature plumbing
# ---------------------------------------------------------------------------

_tl_local = threading.local()


def current_step_timeline() -> StepTimeline | None:
    return getattr(_tl_local, "tl", None)


def _set_current(tl: StepTimeline | None) -> None:
    _tl_local.tl = tl


@contextlib.contextmanager
def engine_phase(name: str) -> Iterator[None]:
    """The span ``areal.train.<name>``, always; and, where the calling
    thread has a step timeline open, the same seconds attributed to it (the
    engine is also used standalone: bench cells, tests). Inside an
    explicitly-opened trainer phase (``tl.phase(...)``) the contribution
    is suppressed: that span already owns the wall time, so e.g. eval
    forwards under ``ckpt_eval`` must not ALSO land in forward_backward."""
    tl = current_step_timeline()
    if tl is None or tl._open_depth > 0:
        with perf_tracer.trace_scope("areal.train." + name):
            yield
    else:
        with tl.phase(name):
            yield


class StepTimelineRecorder:
    """Trainer-side registry of step timelines.

    ``start`` opens the step (and publishes it as the thread's current
    timeline); ``complete`` closes it, observes the catalogued phase
    histograms + utilization gauges, and retains the breakdown in a
    bounded deque. Utilization numbers are optional: callers that know
    the step's token/FLOP content (the RL/SFT trainers) pass them, bare
    harnesses (bench microphases) skip them.
    """

    def __init__(self, max_recent: int = DEFAULT_RECENT_STEPS):
        self._recent: deque[dict] = deque(maxlen=max_recent)
        self._lock = threading.Lock()
        self._started = 0
        self._completed = 0
        self._obs = obs_catalog.train_obs_metrics()

    def start(self, step: int) -> StepTimeline:
        tl = StepTimeline(step)
        with self._lock:
            self._started += 1
        _set_current(tl)
        return tl

    def complete(
        self,
        tl: StepTimeline,
        tokens: float | None = None,
        flops: float | None = None,
        n_chips: int = 1,
        peak_flops_per_chip: float | None = None,
    ) -> dict[str, float]:
        """Close the step; returns the breakdown (the dict the trainer
        folds into its per-step stats/log line).

        ``flops`` is the step's model FLOP content (hw_accounting); MFU is
        reported over the COMPUTE window (forward_backward + optimizer) —
        the hardware-efficiency number the bubble fraction complements —
        plus ``mfu_step`` over the full step wall time (the end-to-end
        utilization the async pipeline is supposed to recover)."""
        if current_step_timeline() is tl:
            _set_current(None)
        bd = tl.breakdown()
        for p in tl.phases:
            self._obs.phase_seconds.labels(phase=p).observe(bd[f"{p}_s"])
        self._obs.phase_seconds.labels(phase="other").observe(bd["other_s"])
        self._obs.bubble_fraction.set(bd["bubble_fraction"])
        chips = max(1, int(n_chips))
        if tokens is not None and tokens > 0 and bd["total_s"] > 0:
            bd["tok_s_per_chip"] = tokens / bd["total_s"] / chips
            self._obs.tokens_per_chip.set(bd["tok_s_per_chip"])
        if (
            flops is not None
            and flops > 0
            and peak_flops_per_chip is not None
            and peak_flops_per_chip > 0
        ):
            compute_s = bd["forward_backward_s"] + bd["optimizer_s"]
            peak = peak_flops_per_chip * chips
            if compute_s > 0:
                bd["mfu"] = min(1.0, flops / (compute_s * peak))
                self._obs.mfu.set(bd["mfu"])
            if bd["total_s"] > 0:
                bd["mfu_step"] = min(1.0, flops / (bd["total_s"] * peak))
        with self._lock:
            self._completed += 1
            self._recent.append(
                {
                    "step": tl.step,
                    "epoch_anchor": tl.epoch_anchor,
                    "breakdown": bd,
                }
            )
        return bd

    def abandon(self, tl: StepTimeline) -> None:
        """Discard an aborted step (preemption mid-step): clears the
        thread-local without observing metrics for a partial step."""
        if current_step_timeline() is tl:
            _set_current(None)

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "started": self._started,
                "completed": self._completed,
                "recent": len(self._recent),
            }

    def recent(self, n: int | None = None) -> list[dict]:
        with self._lock:
            out = list(self._recent)
        if n is None:
            return out
        return out[-n:] if n > 0 else []


def complete_trainer_step(
    recorder: StepTimelineRecorder,
    tl: StepTimeline,
    engine,
    telemetry,
    batch,
    n_extra_forwards: int = 0,
    remat: bool = False,
) -> tuple[dict[str, float], dict | None]:
    """Shared RL/SFT step close: derive the utilization inputs (token
    count from the batch, model-FLOP content from the engine dims, chip
    peak from the device spec / TelemetryConfig override), complete the
    timeline, and refresh the HBM ledger gauges. Returns
    ``(breakdown, ledger-or-None)`` — one implementation so the two
    trainers can never drift."""
    import numpy as np

    from areal_tpu.observability import hw_accounting as hw
    from areal_tpu.utils import logging as alog

    tokens = flops = None
    try:
        tokens = float(np.asarray(batch["attention_mask"]).sum())
    except (KeyError, TypeError):
        pass
    mcfg = getattr(engine, "model_cfg", None)
    if mcfg is not None and tokens:
        flops = hw.train_step_flops(
            mcfg, tokens, n_extra_forwards=n_extra_forwards, remat=remat
        )
    mesh = getattr(engine, "mesh", None)
    bd = recorder.complete(
        tl,
        tokens=tokens,
        flops=flops,
        n_chips=int(getattr(mesh, "size", 1) or 1),
        peak_flops_per_chip=hw.chip_peak_flops(
            override_tflops=telemetry.chip_peak_tflops
        ),
    )
    ledger = None
    if hasattr(engine, "hbm_ledger"):
        try:
            ledger = engine.hbm_ledger(override_hbm_gb=telemetry.chip_hbm_gb)
            hw.observe_hbm_ledger(ledger)
        except Exception:  # noqa: BLE001 — accounting never kills a step
            alog.getLogger("step_timeline").exception(
                "hbm ledger refresh failed"
            )
    return bd, ledger


def format_phase_line(bd: dict[str, float]) -> str:
    """One-line step-phase summary for the trainer log (phases with zero
    time omitted; bubble fraction always shown — it IS the headline)."""
    parts = [f"step {bd['total_s']:.2f}s"]
    for p in PHASES:
        v = bd.get(f"{p}_s", 0.0)
        if v > 0.0005:
            parts.append(f"{p} {v:.2f}s")
    if bd.get("other_s", 0.0) > 0.0005:
        parts.append(f"other {bd['other_s']:.2f}s")
    parts.append(f"bubble {bd.get('bubble_fraction', 0.0):.0%}")
    if "mfu" in bd:
        parts.append(f"mfu {bd['mfu']:.1%}")
    if "tok_s_per_chip" in bd:
        parts.append(f"{bd['tok_s_per_chip']:.0f} tok/s/chip")
    return " | ".join(parts)


def breakdown_stat_keys(bd: dict[str, Any]) -> dict[str, float]:
    """Breakdown -> flat per-step stats keys (``phase/<name>_s`` + the
    utilization scalars) for the stats logger / export_stats surface."""
    out = {f"phase/{p}_s": float(bd.get(f"{p}_s", 0.0)) for p in PHASES}
    out["phase/other_s"] = float(bd.get("other_s", 0.0))
    out["bubble_fraction"] = float(bd.get("bubble_fraction", 0.0))
    for k in ("mfu", "mfu_step", "tok_s_per_chip"):
        if k in bd:
            out[k] = float(bd[k])
    return out
