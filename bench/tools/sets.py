"""Run a cell several times, one process a run, and report the spread of each metric.

    python3 bench/tools/sets.py --workload <name> --seeds 11 12 13 14 15 16 \
        --sets 2 --seconds 40 [--trace 0] [--out runs.jsonl]

Runs ``bench/run.py`` once per seed in each set, the sets one after the
other with the same seeds, and never touches JAX itself, so each child has
the chips to itself. For each metric it prints each set's median, first
and third quartile (``statistics.quantiles(values, n=4)``) and the spread,
(q3 - q1) / median, and 5 times the widest spread. It also prints, over
all runs, the largest value of each number the check compared, and each
run's ``correct``. Every result line goes to ``--out`` as JSON lines.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    m = statistics.median(values)
    return {"median": m, "q1": q1, "q3": q3, "spread": (q3 - q1) / m if m else None}


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t = time.perf_counter()
    out = subprocess.run(
        [sys.executable, str(RUN), "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    wall = time.perf_counter() - t
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if out.returncode == 0 and lines else None
    return {"seed": seed, "rc": out.returncode, "process_s": wall, "result": result,
            "stderr_tail": out.stderr[-1500:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    runs = []
    for s in range(args.sets):
        for seed in args.seeds:
            r = one_run(args.workload, seed, args.seconds, args.trace)
            r["set"] = s
            runs.append(r)
            res = r["result"] or {}
            print(json.dumps({"set": s, "seed": seed, "rc": r["rc"],
                              "process_s": round(r["process_s"], 3),
                              "correct": res.get("correct"),
                              "attempted": res.get("attempted"),
                              "metrics": {k: v["value"] for k, v in res.get("metrics", {}).items()},
                              "checks": {k: v["value"] for k, v in res.get("checks", {}).items()},
                              "peak": (res.get("device") or {}).get("memory_peak_bytes"),
                              "log": [x for x in r["stderr_tail"].splitlines()
                                      if x.startswith(("set-up", "window"))]}),
                  flush=True)
            if r["rc"] != 0 or not res.get("correct"):
                print(r["stderr_tail"], flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(r) + "\n")

    ok = [r for r in runs if r["result"]]
    names = sorted({k for r in ok for k in r["result"]["metrics"]})
    summary = {}
    for name in names:
        per_set = []
        for s in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in ok
                    if r["set"] == s and name in r["result"]["metrics"]]
            if len(vals) >= 2:
                per_set.append(spread(vals))
        if per_set and all(p["spread"] is not None for p in per_set):
            widest = max(p["spread"] for p in per_set)
            summary[name] = {"sets": per_set, "widest_spread": widest, "five_times": 5 * widest}
    worst = {}
    for r in ok:
        for k, v in r["result"].get("checks", {}).items():
            worst[k] = max(worst.get(k, 0.0), v["value"])
    print(json.dumps({"summary": summary, "check_worst": worst,
                      "all_correct": all(r["result"]["correct"] for r in ok),
                      "runs": len(runs), "ok_runs": len(ok)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
