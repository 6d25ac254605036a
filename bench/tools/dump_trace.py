"""Print what a profiler trace holds, to read it by hand before writing a metric.

    python3 bench/tools/dump_trace.py <file.xplane.pb> [--top 25]

For each plane: its lines with their event counts, and per line the names
that took most time (summed duration).
"""

from __future__ import annotations

import argparse
import collections


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(args.path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            span = (min(e.start_ns for e in events), max(e.start_ns + e.duration_ns for e in events)) if events else (0, 0)
            print(f"  LINE {line.name!r}: {len(events)} events, {len(total)} names, span {span}")
            for name, ns in total.most_common(args.top):
                print(f"    {ns / 1e6:12.3f} ms  x{count[name]:<6d} {name[:160]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
