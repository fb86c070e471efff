"""When the decode loop commits the next chunk's batch (docs/serving.md,
"The pass and its commit point").

With a chunk running on the device, the next chunk only has to be queued
before the running one ends. Whatever is admitted before that instant rides
the next chunk; whatever comes after waits a whole chunk more. So the loop
commits part-way through the running chunk, not at its start, and for that it
needs the chunk's wall time. It observes it: the interval between two
consecutive returns of the loop's blocking pull is the time of the chunk that
ran between them (plus any prefill queued before it).

Pure arithmetic on numbers handed in: no clock, no thread, no device.
"""

from __future__ import annotations

from collections import deque
from typing import Hashable

# The commit point's place inside the running chunk. One half leaves the host
# half a chunk (125-250 ms in the benchmark's rollout cells, against 4-7 ms of
# host work a pass) and a chunk that a leaving group makes 15% shorter cannot
# outrun it. Chosen on the chip (PERF.md, PR 31), not a configuration field.
COMMIT_FRACTION = 0.5
# The estimate is the least of this many intervals: a prefill queued between
# two chunks lengthens one interval, never all of them. Since PR 47 the loop
# also says which intervals a prefill lengthened (``pulled(laden=True)``) and
# those are left out: at the start of a long-context traffic EVERY early
# interval holds prompt passes (eight prompts of 4k-16k tokens: 6.45 s where a
# chunk is 0.34), the least of them was taken for a chunk's time, and the loop
# held 3.2 + 1.6 + 0.8 s with the chip idle (PERF.md section 6, PR 47).
KEPT_INTERVALS = 4
# The slack left after the commit point has to cover the largest host time of
# this many passes this many times over, or the loop does not hold at all (a
# tiny engine whose chunk is a few host-times long behaves as without this).
KEPT_HOST_TIMES = 8
SLACK_HOST_MULTIPLE = 4.0


class ChunkPacer:
    """A chunk's wall time from the pulls' return times, and from it the
    instant at which the loop should commit the next chunk's batch."""

    def __init__(self) -> None:
        self._key: Hashable | None = None
        self._last_pull: float | None = None
        self._intervals: deque[float] = deque(maxlen=KEPT_INTERVALS)
        self._host: deque[float] = deque(maxlen=KEPT_HOST_TIMES)

    def reset(self) -> None:
        """The device went idle or ran something else (first chunk, idle
        poll, pause, hold fence, released cache, speculative round, a chunk
        drained early by a reap): the next interval is not a chunk's time."""
        self._key = None
        self._last_pull = None
        self._intervals.clear()

    def pulled(self, at: float, key: Hashable, laden: bool = False) -> None:
        """The blocking pull of a chunk of program ``key`` returned at ``at``
        while the next chunk was queued behind it. ``laden``: a prefill was
        queued before that chunk, so the interval it ends is no chunk's time."""
        if key != self._key:
            # another program (window, sampler variant): another time
            self._intervals.clear()
            self._key = key
        elif self._last_pull is not None and not laden:
            self._intervals.append(at - self._last_pull)
        self._last_pull = at

    def host_work(self, seconds: float) -> None:
        """Host seconds of one pass outside its waits for the device."""
        self._host.append(max(0.0, seconds))

    def estimate(self) -> float | None:
        """A chunk's wall seconds, or None without one."""
        return min(self._intervals) if self._intervals else None

    def commit_point(self, key: Hashable) -> float | None:
        """The instant (on the clock of ``pulled``) at which to commit the
        batch of the chunk after the running one, whose program is ``key``.
        None means now: no estimate for this program, or too little slack
        behind the commit point for the host's own work."""
        est = self.estimate()
        if est is None or key != self._key or not self._host:
            return None
        if (1.0 - COMMIT_FRACTION) * est < SLACK_HOST_MULTIPLE * max(self._host):
            return None
        return self._last_pull + COMMIT_FRACTION * est
