"""The engine's own host spans in a traced window, for the metrics that read them.

``MapReduceJob.run`` opens ``jax.profiler.TraceAnnotation`` spans on the
thread that calls it (``src/repro/core/spans.py`` lists them and their
counters); the harness calls it on the thread that opened ``bench.window``,
whose events ``trace_reduce`` keeps in ``run.trace.host``. The names are
written out here, not imported, so a checkout whose engine records no
spans is read too: :func:`read` then returns ``None`` and each metric that
reads it is left out of the result line.

Two readings of a span:

- host time: the summed duration of the span's events in the window;
- idle time under a span: per chip, the part of the union of the span's
  events during which the chip runs no ``XLA Ops`` event, averaged over
  chips. Time the host spends waiting on a running device is not idle.

Both are divided by the window's batches and given in ms.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from trace_reduce import Interval, TraceError, TracedRun, clip, idle_gaps

BATCH = "os4m.batch"
DECIDE = "os4m.decide"
STATS_PULL = "os4m.stats_pull"
PLAN = "os4m.plan"
OUTPUT_PULL = "os4m.output_pull"
MERGE = "os4m.merge"


def disjoint(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    """The union of the intervals as sorted, disjoint ``(start, end)`` pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint lists of stretches."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class ProgramSpans:
    """The window's ``os4m.*`` host spans and the chips' idle stretches."""

    run: TracedRun
    spans: List[Interval]
    gaps: List[List[Tuple[float, float]]]  # per chip

    def named(self, *names: str) -> List[Interval]:
        return [iv for iv in self.spans if iv[0] in names]

    def host_ms(self, *names: str) -> float:
        """Summed duration of the spans named ``names``, per batch, in ms."""
        ns = sum(e - s for _, s, e in self.named(*names))
        return ns / self.run.num_batches * 1e-6

    def idle_ms(self, *names: str) -> float:
        """Chip idle time under the union of the spans ``names``, per batch,
        in ms, averaged over chips."""
        under = disjoint(self.named(*names))
        per_chip = [overlap_ns(g, under) for g in self.gaps]
        return sum(per_chip) / len(per_chip) / self.run.num_batches * 1e-6


def read(run: TracedRun) -> Optional[ProgramSpans]:
    """The program's spans of ``run``, or ``None`` when it recorded none.

    Each batch of the window has to have its one ``os4m.batch`` span, and
    each batch that planned afresh (``reused`` false) an ``os4m.plan``
    inside it; a trace that breaks either is an error, never a reading.
    """
    trace = run.trace
    spans = [iv for iv in clip(trace.host, trace.window) if iv[0].startswith("os4m.")]
    batches = sorted((iv for iv in spans if iv[0] == BATCH), key=lambda iv: iv[1])
    if not batches:
        return None
    if len(batches) != run.num_batches:
        raise TraceError(f"{len(batches)} {BATCH!r} spans in the window, "
                         f"{run.num_batches} batches")
    plans = [iv for iv in spans if iv[0] == PLAN]
    for i, (b, rec) in enumerate(zip(batches, run.batches)):
        if not rec["reused"] and not any(b[1] <= p[1] and p[2] <= b[2] for p in plans):
            raise TraceError(f"batch {i} of the window planned afresh but has no "
                             f"{PLAN!r} span")
    gaps = [idle_gaps(d.ops, trace.window) for d in trace.devices]
    return ProgramSpans(run, spans, gaps)
