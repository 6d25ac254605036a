"""From a profiler trace to device times: the reduction every per-layer metric reads.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` and keeps, for each TPU, the events of its
``XLA Modules`` line (one per executable run, named after the jitted
function, e.g. ``jit_phase_b(...)``) and of its ``XLA Ops`` line (one per
HLO operation, named by the instruction, e.g. ``fusion.77``), and the events of the host thread on which the harness
wrote its own spans (``bench.window``, ``bench.batch``). Device and host
events share one clock in the trace.

A name that matches nothing is an error, never a zero.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)


class TraceError(RuntimeError):
    """The trace lacks what a metric needs: a plane, a line or a name."""


@dataclasses.dataclass
class Device:
    """One chip's events."""

    index: int
    modules: List[Interval]
    ops: List[Interval]


@dataclasses.dataclass
class Trace:
    """The devices' and the host thread's events of one traced window."""

    devices: List[Device]
    host: List[Interval]
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def _events(line, name=lambda n: n) -> List[Interval]:
    return [(name(e.name), float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%fusion.77 = s32[...]
    fusion(...)``; keep the instruction's own name, ``fusion.77``."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_profile(data, devices: Optional[Sequence[int]] = None) -> Trace:
    """Reduce a ``ProfileData``; ``devices`` keeps only those TPU ids."""
    devs, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(1))
            if devices is not None and idx not in devices:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            missing = [n for n in (MODULES_LINE, OPS_LINE) if n not in lines]
            if missing:
                raise TraceError(f"plane {plane.name} has no line {missing}; "
                                 f"lines: {sorted(lines)}")
            devs.append(Device(idx, _events(lines[MODULES_LINE]),
                               _events(lines[OPS_LINE], op_name)))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                events = _events(ln)
                if any(e[0] == WINDOW_SPAN for e in events):
                    host.extend(events)
    if not devs:
        raise TraceError(f"no TPU plane in the trace; planes: {[p.name for p in data.planes]}")
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise TraceError(f"expected one {WINDOW_SPAN!r} span on one host thread, "
                         f"found {len(windows)}")
    devs.sort(key=lambda d: d.index)
    host.sort(key=lambda e: (e[1], -e[2]))
    return Trace(devs, host, (windows[0][1], windows[0][2]))


def load(path, devices: Optional[Sequence[int]] = None) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        import gzip

        return from_profile(ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes())), devices)
    return from_profile(ProfileData.from_file(str(path)), devices)


def find_xplane(log_dir) -> Path:
    """The one ``.xplane.pb`` a ``jax.profiler.trace(log_dir)`` session wrote."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise TraceError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def clip(intervals: Iterable[Interval], window: Tuple[float, float]) -> List[Interval]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals if e > lo and s < hi]


def union_ns(intervals: Iterable[Interval]) -> float:
    """Length of the union of the intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Interval], window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The stretches of ``window`` that no interval covers."""
    gaps, t = [], window[0]
    for _, s, e in sorted(clip(intervals, window), key=lambda x: x[1]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def matching(intervals: Iterable[Interval], pattern: str, what: str) -> List[Interval]:
    """Events whose name matches ``pattern`` (``re.search``); none is an error."""
    rx = re.compile(pattern)
    out = [iv for iv in intervals if rx.search(iv[0])]
    if not out:
        names = sorted({iv[0] for iv in intervals})[:20]
        raise TraceError(f"no {what} event matches {pattern!r}; some names: {names}")
    return out


def module_ns(trace: Trace, pattern: str) -> List[float]:
    """Per device, the summed duration of the executables matching ``pattern``
    in the window."""
    return [sum(e - s for _, s, e in clip(matching(d.modules, pattern, "XLA Modules"),
                                           trace.window))
            for d in trace.devices]


def busy_ns(trace: Trace) -> List[float]:
    """Per device, the union of its op intervals in the window."""
    return [union_ns(clip(d.ops, trace.window)) for d in trace.devices]


class HostSpans:
    """The host thread's spans as a tree, to ask what the host did at a time."""

    def __init__(self, host: Sequence[Interval]):
        self.events = sorted(host, key=lambda e: (e[1], -e[2]))
        self.starts = [e[1] for e in self.events]
        self.parent = []
        stack: List[int] = []
        for i, (_, s, e) in enumerate(self.events):
            while stack and self.events[stack[-1]][2] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def label(self, t: float) -> str:
        """The two innermost spans covering ``t``, outer first, below the window."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.events[i][2] <= t:
            i = self.parent[i]
        chain = []
        while i >= 0:
            if self.events[i][0] != WINDOW_SPAN:
                chain.append(self.events[i][0])
            i = self.parent[i]
        return " > ".join(reversed(chain[:2])) if chain else "(no host span)"


def _module_of(modules: Sequence[Interval]):
    """A lookup from a time to the executable running then, by short name."""
    spans = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in spans]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < spans[i][2]:
            return spans[i][0].split("(", 1)[0]
        return "(no module)"
    return find


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time, and idle time by host activity.

    ``device_ops``: per op, named ``<executable>/<instruction>`` (an
    instruction's name is unique only within its executable), its summed
    duration in the window, averaged over the devices, in seconds.
    ``idle_gaps``: per label of what the host was doing at each gap's
    middle, the summed idle seconds, averaged over the devices.
    """
    n = len(trace.devices)
    spans = HostSpans(trace.host)
    ops: Dict[str, float] = collections.defaultdict(float)
    gaps: Dict[str, float] = collections.defaultdict(float)
    for d in trace.devices:
        module = _module_of(d.modules)
        for name, s, e in clip(d.ops, trace.window):
            ops[f"{module(s)}/{name}"] += (e - s) / n
        for s, e in idle_gaps(d.ops, trace.window):
            gaps[spans.label((s + e) / 2)] += (e - s) / n
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in rank(ops)],
            "idle_gaps": [[k, v * 1e-9] for k, v in rank(gaps)]}


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric reads: the trace of the window and the run's record.

    ``batches`` holds one record per batch of the window (``reused``,
    ``wall_s``, ``pool``); ``floors`` the matching phase-B floors
    (``bench/floors.py``).
    """

    trace: Trace
    batches: List[dict]
    floors: List[dict]

    @property
    def num_batches(self) -> int:
        return len(self.batches)

    @property
    def window_s(self) -> float:
        return self.trace.window_ns * 1e-9

    @property
    def busy_s(self) -> float:
        busy = busy_ns(self.trace)
        return sum(busy) / len(busy) * 1e-9

    @property
    def floor_bound(self) -> str:
        return max(self.floors, key=lambda f: f["floor_s"])["bound"]

    def trace_module_ns(self, pattern: str) -> List[float]:
        return module_ns(self.trace, pattern)
