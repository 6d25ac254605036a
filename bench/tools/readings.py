"""Readings that the limits of the correctness check are set from.

    python3 bench/tools/readings.py --workload <name> --seeds 1 2 3 \
        [--program-seconds 16]

For each seed, in one process:

- the program: one whole run of the cell (``run.run_cell``) with a window
  of ``--program-seconds``, its compared numbers as the run's check
  reports them: the lower readings;
- the control: the cell's pool drawn exactly as a run draws it, and the
  job's ``control`` (the reference one precision step below the
  configuration's) put in the program's place, through the same
  comparison with the float64 reference: the upper readings.

Prints one JSON line per seed, then one summary line with the largest
program reading and the least control reading of each number. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import checks  # noqa: E402
import layout  # noqa: E402
import run  # noqa: E402


def control_readings(cell, seed: int) -> dict:
    """The control's compared numbers over the pool of ``seed``."""
    import jax
    import numpy as np

    cfg = cell.config
    job_module, traffic = layout.job_module(cell), layout.traffic_module(cell)
    m, k = int(cfg["engine"]["num_slots"]), int(cfg["rows_per_shard"])
    sharding, _, _ = run.pool_sharding(jax, np, cell.chips)
    pool = jax.device_get(traffic.make_pool(cell.traffic, job_module, cfg["job"], seed,
                                            (m, k), sharding))
    n = int(cfg["engine"]["num_clusters"])
    verdict = checks.Verdict(checks.limits_of(cfg))
    for batch in pool:
        verdict.add(checks.compare(checks.rows(job_module.control(batch, n)),
                                   checks.rows(job_module.reference(batch, n)), 0))
    return {"batches": verdict.attempted, "passes": verdict.correct, "worst": verdict.worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seconds", type=float, default=16.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in args.seeds:
        res = run.run_cell(types.SimpleNamespace(
            workload=args.workload, seed=seed, seconds=args.program_seconds,
            trace=0, trace_out=None))
        row = {"seed": seed,
               "program": {"correct": res["correct"], "attempted": res["attempted"],
                           "worst": {k: v["value"] for k, v in res["checks"].items()}},
               "control": control_readings(layout.load_cell(args.workload), seed)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({
        "program_largest": {k: max(r["program"]["worst"][k] for r in rows) for k in checks.NAMES},
        "program_correct_on_every_seed": all(r["program"]["correct"] for r in rows),
        "control_least": {k: min(r["control"]["worst"][k] for r in rows) for k in checks.NAMES},
        "control_rejected_on_every_seed": not any(r["control"]["passes"] for r in rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
