"""Staleness-bounded rollout admission control.

Behavioral parity with reference areal/infra/staleness_manager.py:18-162: the
capacity formula (:97-111) bounds how many rollouts may run concurrently so
no accepted trajectory is more than ``max_staleness`` versions behind the
policy that will train on it:

    capacity = min(max_concurrent - running,
                   (max_staleness + version + 1) * consumer_bs
                     - (accepted + running))

``version`` comes from a VersionProvider protocol (the inference engine).
"""

from __future__ import annotations

import threading
from typing import Protocol

from areal_tpu.api.io_struct import RolloutStat
from areal_tpu.observability import catalog

# ---------------------------------------------------------------------------
# Version-lag bucket vocabulary (docs/observability.md "Learning-health
# observatory"). ONE definition shared by the loss-side bucket stats
# (trainer/ppo.py), the metric catalog's ``lag_bucket`` label values, the
# autopilot's learning-health guard signal, and the dashboard panel — the
# four must agree on what "the high-lag bucket" means or the guard steers
# on a bucket nobody computes.
#
# lag = consuming policy version - per-token policy version. Buckets:
#   "0"  : lag <= 0 (on-policy; unknown/untagged tokens clamp here)
#   "1"  : lag == 1 (one weight commit behind — the η=1 steady state)
#   "2"  : 2 <= lag <= 3
#   "4+" : lag >= 4 (the deep-off-policy tail the staleness bound exists
#          to keep useful; the guard watches this bucket)
# ---------------------------------------------------------------------------
LAG_BUCKET_EDGES = (0, 1, 2, 4)
LAG_BUCKET_LABELS = ("0", "1", "2", "4+")
HIGH_LAG_BUCKET = "4+"


def lag_bucket_index(lag: int) -> int:
    """Bucket index of one lag value (host-side twin of the in-jit
    bucketing in trainer/ppo.py — keep both in sync with the edges)."""
    if lag >= 4:
        return 3
    if lag >= 2:
        return 2
    if lag >= 1:
        return 1
    return 0


class VersionProvider(Protocol):
    def get_version(self) -> int: ...


class StalenessManager:
    def __init__(
        self,
        version_provider: VersionProvider,
        max_concurrent_rollouts: int,
        consumer_batch_size: int,
        max_staleness: int = 0,
    ):
        self._vp = version_provider
        self.max_concurrent_rollouts = max_concurrent_rollouts
        self.consumer_batch_size = consumer_batch_size
        self.max_staleness = max_staleness
        self._lock = threading.Lock()
        self.stat = RolloutStat()
        self._metrics = catalog.staleness_metrics()

    def get_capacity(self) -> int:
        with self._lock:
            version = self._vp.get_version()
            concurrency_cap = self.max_concurrent_rollouts - self.stat.running
            staleness_cap = (
                (self.max_staleness + version + 1) * self.consumer_batch_size
                - self.stat.accepted
                - self.stat.running
            )
            capacity = min(concurrency_cap, staleness_cap)
            self._metrics.capacity.set(capacity)
            self._metrics.running.set(self.stat.running)
            return capacity

    # -- accounting (called by the dispatcher) ----------------------------
    def on_submit(self, n: int = 1) -> None:
        with self._lock:
            self.stat.submitted += n
            self.stat.running += n
        self._metrics.submitted.inc(n)

    def on_accept(self, n: int = 1) -> None:
        with self._lock:
            self.stat.running -= n
            self.stat.accepted += n
        self._metrics.accepted.inc(n)

    def on_reject(self, n: int = 1) -> None:
        with self._lock:
            self.stat.running -= n
            self.stat.rejected += n
        self._metrics.rejected.inc(n)

    def restore_accepted(self, n: int = 1) -> None:
        """Recovery-time accounting restoration (trajectory-journal
        replay, docs/fault_tolerance.md): the trajectories were submitted
        AND accepted in a previous life, so only the accepted count
        re-enters the capacity formula — the staleness bound re-tightens
        exactly as before the crash, while the cumulative
        submitted/accepted *counters* (which the stats pipeline exports as
        this-life throughput) are not inflated by re-counting old work."""
        if n <= 0:
            return
        with self._lock:
            self.stat.accepted += n

    def set_max_staleness(self, n: int) -> int:
        """Goodput-autopilot hook (docs/autopilot.md): retune the
        staleness bound live. Takes effect at the next ``get_capacity``
        call — in-flight rollouts are never clawed back; a tightened
        bound simply stops admitting until the accepted backlog drains
        under the new formula. Clamped at >= 0; returns the applied
        value."""
        with self._lock:
            self.max_staleness = max(0, int(n))
            return self.max_staleness

    def observe_version_lag(self, lag: int) -> None:
        """Record an accepted trajectory's version lag (current policy
        version minus the oldest per-token version in the trajectory) —
        the drifting-version-mix signal the staleness bound exists for."""
        self._metrics.version_lag.observe(max(0, lag))

    def observe_version_span(self, span: int) -> None:
        """Record an accepted trajectory's per-token version spread (max -
        min tagged version). Under zero-pause weight sync a sequence that
        decodes across a commit carries BOTH versions token-by-token; span
        > 0 counts it as a mixed-version trajectory — exactly the
        population decoupled PPO's per-token importance correction exists
        for (SURVEY §3.4)."""
        self._metrics.version_span.observe(max(0, span))
        if span > 0:
            self._metrics.mixed_version.inc()

    def export_stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "submitted": self.stat.submitted,
                "running": self.stat.running,
                "accepted": self.stat.accepted,
                "rejected": self.stat.rejected,
            }
