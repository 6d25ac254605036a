"""Print, per batch, what the engine's spans and scopes say about a traced window.

    python3 bench/tools/span_report.py <file.xplane.pb[.gz]>

for a trace kept by ``bench/run.py --trace 1 --trace-out <dir>``. For each
batch of the window (each ``os4m.batch`` span), it prints:

- each ``os4m.*`` host span: its host time and the device idle time under
  it (mean over chips), with its counters (bytes pulled, ``valid_pairs`` /
  ``input_pairs`` of the plan, the jit key of a build);
- per chip, the device time of the phase-B scopes ``os4m.spill``,
  ``os4m.copy`` and ``os4m.reduce`` (the union of the ops whose ``tf_op``
  path names the scope).

At the end it prints each scope's device time per batch per chip, and for
``os4m.reduce`` the largest over the mean across chips: the imbalance of
the Reduce work that OS4M's schedule is meant to remove.

``jax.profiler.ProfileData`` gives no access to event metadata, where the
device plane keeps each op's ``tf_op`` (its ``jax.named_scope`` path), so
this reads that part of the ``XSpace`` protobuf with a small wire-format
reader.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

SCOPES = ("os4m.spill", "os4m.copy", "os4m.reduce")
_SCOPE_RX = {s: re.compile(r"(^|[/(;])" + re.escape(s) + r"([/);]|$)") for s in SCOPES}


# -- protobuf wire format ---------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int or a bytes view."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _map_entries(entries) -> Dict[int, object]:
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def device_scoped_ops(raw: bytes) -> Dict[int, List[Tuple[str, float, float]]]:
    """Per TPU id, the ``XLA Ops`` events as ``(tf_op, start_ns, end_ns)``.

    XSpace: planes = 1. XPlane: name = 2, lines = 3, event_metadata = 4
    (map), stat_metadata = 5 (map). XLine: name = 2, timestamp_ns = 3,
    events = 4. XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3.
    XEventMetadata: stats = 5. XStat: metadata_id = 1, str_value = 5,
    ref_value = 7 (the id of a stat metadata whose name is the string).
    """
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        parts = collections.defaultdict(list)
        for f, v in _fields(plane):
            parts[f].append(v)
        m = trace_reduce.DEVICE_PLANE.match(bytes(parts[2][0]).decode() if parts[2] else "")
        if not m:
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_entries(parts[5]).items()}
        tf_op = {}
        for k, meta in _map_entries(parts[4]).items():
            for f, stat in _fields(meta):
                s = dict(_fields(stat)) if f == 5 else {}
                if s and stat_names.get(s.get(1)) == "tf_op":
                    tf_op[k] = (bytes(s[5]).decode() if 5 in s
                                else stat_names.get(s.get(7), ""))
        ops = []
        for line in parts[3]:
            lf = collections.defaultdict(list)
            for f, v in _fields(line):
                lf[f].append(v)
            if not lf[2] or bytes(lf[2][0]).decode() != trace_reduce.OPS_LINE:
                continue
            t0 = lf[3][0] if lf[3] else 0
            for ev in lf[4]:
                e = dict(_fields(ev))
                start = t0 + e.get(2, 0) / 1000.0
                ops.append((tf_op.get(e.get(1), ""), start, start + e.get(3, 0) / 1000.0))
        out[int(m.group(1))] = ops
    return out


# -- the report ---------------------------------------------------------------

def _host_events(data) -> List[Tuple[str, float, float, dict]]:
    """The ``os4m.*`` events, with their stats, of the thread that opened the window."""
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == trace_reduce.WINDOW_SPAN for e in events):
                return sorted(((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                                dict(e.stats)) for e in events if e.name.startswith("os4m.")),
                              key=lambda e: (e[1], -e[2]))
    raise trace_reduce.TraceError(f"no host thread holds a {trace_reduce.WINDOW_SPAN!r} span")


def report(path) -> dict:
    """Per batch: host spans (host ms, idle ms, counters) and per-chip scope ms."""
    from jax.profiler import ProfileData

    path = Path(path)
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    trace = trace_reduce.from_profile(data)
    events = [e for e in _host_events(data)
              if e[2] > trace.window[0] and e[1] < trace.window[1]]
    gaps = [trace_reduce.idle_gaps(d.ops, trace.window) for d in trace.devices]
    scoped = device_scoped_ops(raw)
    chips = [d.index for d in trace.devices]
    batches = []
    for b in (e for e in events if e[0] == program_spans.BATCH):
        inner = [e for e in events if b[1] <= e[1] and e[2] <= b[2] and e is not b]
        spans = []
        for name, s, e, stats in [b] + inner:
            under = [(s, e)]
            idle = sum(program_spans.overlap_ns(g, under) for g in gaps) / len(gaps)
            spans.append({"name": name, "host_ms": (e - s) * 1e-6, "idle_ms": idle * 1e-6,
                          "counters": stats})
        scopes = {}
        for chip in chips:
            ops = [op for op in scoped.get(chip, []) if b[1] <= op[1] < b[2]]
            scopes[chip] = {
                s: trace_reduce.union_ns([op for op in ops if rx.search(op[0])]) * 1e-6
                for s, rx in _SCOPE_RX.items()}
        batches.append({"batch": b[3].get("batch"), "spans": spans, "scopes": scopes})
    if not batches:
        raise trace_reduce.TraceError(f"no {program_spans.BATCH!r} span in the window")
    mean_scope = {c: {s: sum(bt["scopes"][c][s] for bt in batches) / len(batches)
                      for s in SCOPES} for c in chips}
    reduce_ms = [mean_scope[c]["os4m.reduce"] for c in chips]
    mean_reduce = sum(reduce_ms) / len(reduce_ms)
    return {"chips": chips, "batches": batches, "scope_ms_per_batch": mean_scope,
            "reduce_max_over_mean": max(reduce_ms) / mean_reduce if mean_reduce else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    args = ap.parse_args(argv)
    rep = report(args.path)
    for bt in rep["batches"]:
        print(f"batch {bt['batch']}")
        print(f"  {'span':<22} {'host ms':>10} {'idle ms':>10}  counters")
        for sp in bt["spans"]:
            counters = " ".join(f"{k}={v}" for k, v in sp["counters"].items())
            print(f"  {sp['name']:<22} {sp['host_ms']:10.3f} {sp['idle_ms']:10.3f}  {counters}")
        for chip, scopes in bt["scopes"].items():
            print(f"  chip {chip}: " + "  ".join(f"{s} {ms:.3f} ms" for s, ms in scopes.items()))
    print("per batch, mean over the window:")
    for chip, scopes in rep["scope_ms_per_batch"].items():
        print(f"  chip {chip}: " + "  ".join(f"{s} {ms:.3f} ms" for s, ms in scopes.items()))
    print(f"os4m.reduce max/mean over chips: {rep['reduce_max_over_mean']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
