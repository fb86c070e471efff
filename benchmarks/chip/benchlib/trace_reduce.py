"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

Everything the per-layer readers and the result line's ``device`` and
``breakdown`` keys take from the device trace goes through here, so that
every PR computes the same number in the same way:

  busy/idle   union of the intervals in which an operation ran on a device
  by name     device seconds per XLA op and per XLA module (jitted program)
  idle gaps   the longest gaps in the union, each with what the host's
              threads were doing inside it

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A TPU
trace has one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops``
holds one event per executed op and whose line ``XLA Modules`` holds one
event per executed program; host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# HLO ops that only contain other ops: their events span their children, so
# they are left out of the busy union and of the list of ops by time
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class DeviceTrace:
    name: str
    ops: list[tuple[str, float, float]] = field(default_factory=list)  # name, start_s, dur_s
    modules: list[tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: list[DeviceTrace]
    host: list[tuple[str, str, float, float]]  # thread, name, start_s, dur_s
    t_min: float
    t_max: float

    @property
    def window_s(self) -> float:
        return max(0.0, self.t_max - self.t_min)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    t_min, t_max = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and plane.name != HOST_PLANE:
            continue
        dev = DeviceTrace(plane.name) if is_dev else None
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                t_min, t_max = min(t_min, s), max(t_max, s + d)
                if not is_dev:
                    host.append((line.name, ev.name, s, d))
                elif line.name == OPS_LINE:
                    dev.ops.append((ev.name, s, d))
                else:
                    dev.modules.append((ev.name, s, d))
        if dev is not None:
            devices.append(dev)
    if t_min == float("inf"):
        t_min = t_max = 0.0
    return Trace(devices, host, t_min, t_max)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaf_ops(dev: DeviceTrace) -> list[tuple[str, float, float]]:
    return [ev for ev in dev.ops if not CONTAINER.match(ev[0])]


def busy_intervals(dev: DeviceTrace) -> list[tuple[float, float]]:
    return union([(s, s + d) for _, s, d in leaf_ops(dev)])


def short_name(op: str) -> str:
    """``%fusion.302 fusion bf16[128,1536]`` from an op's full HLO text."""
    name, eq, rest = op.partition(" = ")
    code = _OPCODE.search(rest)
    if not eq or not code:
        return op[:120]
    t, shape = _TARGET.search(rest), _SHAPE.search(rest)
    return f"{name} {code.group(1)}{':' + t.group(1) if t else ''} {shape.group(0) if shape else ''}".strip()[:120]


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over the chips."""
    if not trace.devices:
        return 0.0
    per = [sum(e - s for s, e in busy_intervals(d)) for d in trace.devices]
    return sum(per) / len(per)


def seconds_by_name(events, pattern: str | None = None) -> dict[str, tuple[float, int]]:
    """{name: (seconds, count)} over (name, start, dur) events whose name
    matches ``pattern`` (all events if None)."""
    rx = re.compile(pattern) if pattern else None
    out: dict[str, list[float]] = {}
    for name, _, d in events:
        if rx is None or rx.search(name):
            acc = out.setdefault(name, [0.0, 0])
            acc[0] += d
            acc[1] += 1
    return {k: (v[0], int(v[1])) for k, v in out.items()}


def matched(trace: Trace, line: str, pattern: str, within: str | None = None) -> tuple[float, int]:
    """(seconds, events) of ops or modules matching ``pattern``, averaged
    over the chips. ``line`` is "ops" or "modules". ``within`` keeps only
    the ops that start inside a run of a program matching it (a kernel that
    has no name of its own is told apart by the program it runs in)."""
    if not trace.devices:
        return 0.0, 0
    tot_s, tot_n = 0.0, 0
    for d in trace.devices:
        events = leaf_ops(d) if line == "ops" else d.modules
        if within is not None:
            rx = re.compile(within)
            spans = union([(s, s + du) for n, s, du in d.modules if rx.search(n)])
            starts = [a for a, _ in spans]
            kept = []
            for ev in events:
                i = bisect.bisect_right(starts, ev[1]) - 1
                if i >= 0 and ev[1] < spans[i][1]:
                    kept.append(ev)
            events = kept
        for s, n in seconds_by_name(events, pattern).values():
            tot_s += s
            tot_n += n
    return tot_s / len(trace.devices), tot_n // len(trace.devices)


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[name, seconds], ...]: the device ops that took most time (chip 0)."""
    if not trace.devices:
        return []
    by = seconds_by_name(leaf_ops(trace.devices[0]))
    rows = sorted(((k, v[0]) for k, v in by.items()), key=lambda kv: -kv[1])[:n]
    return [[short_name(k), s] for k, s in rows]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """[[what the host was doing, seconds], ...] for the longest gaps in
    chip 0's busy union inside the traced window. A gap is named by the
    host event that overlaps it longest (``thread:event``), or ``host:none``
    where no host event touches it."""
    if not trace.devices:
        return []
    busy = busy_intervals(trace.devices[0])
    edges = [trace.t_min] + [x for se in busy for x in se] + [trace.t_max]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for gs, ge in gaps:
        best, best_ov = "host:none", 0.0
        for thread, name, s, d in trace.host:
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = f"{thread.split('/')[0]}:{name}"[:120], ov
        out.append([best, ge - gs])
    return out


def summary(trace: Trace) -> dict:
    return {
        "busy_s": busy_seconds(trace),
        "window_s": trace.window_s,
        "breakdown": {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)},
    }


def main(argv: list[str]) -> int:
    """``python3 trace_reduce.py <file.xplane.pb>``: what is in a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(argv[1])
    for plane in pd.planes:
        print("plane", plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines][:12])
    tr = load(argv[1])
    print(f"window {tr.window_s:.4f}s busy {busy_seconds(tr):.4f}s over {len(tr.devices)} device(s)")
    for d in tr.devices[:1]:
        mods = sorted(seconds_by_name(d.modules).items(), key=lambda kv: -kv[1][0])[:15]
        print("programs:", *[f"\n  {s:.4f}s x{n} {k[:100]}" for k, (s, n) in mods])
        ops = sorted(seconds_by_name(leaf_ops(d)).items(), key=lambda kv: -kv[1][0])[:25]
        print("ops:", *[f"\n  {s:.4f}s x{n} {short_name(k)}" for k, (s, n) in ops])
    print("idle gaps:", idle_gaps(tr))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
