"""The comparison that decides ``correct``: program outputs against the plain reference.

Three numbers are compared, each against its own limit from the
configuration's ``limits``:

- ``value_rel_err``: the widest gap of an output value from the float64
  reference, over every group of every checked batch, as a share of the
  reference value (of 1 where the reference is 0, an empty group);
- ``count_mismatch``: groups whose pair count differs from the reference
  (counts are exact in float32 below 2^24, so the limit is 0);
- ``overflow``: pairs the engine reports as dropped (limit 0).

A batch whose numbers pass a limit counts as failed. A value that is not
finite reads as infinitely far off.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

NAMES = ("value_rel_err", "count_mismatch", "overflow")


def compare_batch(values, counts, overflow, ref_values, ref_counts) -> Dict[str, float]:
    """The compared numbers of one batch."""
    values = np.asarray(values, np.float64).reshape(ref_values.shape)
    counts = np.asarray(counts, np.float64).reshape(ref_counts.shape)
    gap = np.abs(values - ref_values) / np.maximum(np.abs(ref_values), 1.0)
    rel = float(np.max(gap)) if gap.size else 0.0
    if not np.isfinite(rel):
        rel = float("inf")
    return {
        "value_rel_err": rel,
        "count_mismatch": float(np.count_nonzero(counts != ref_counts)),
        "overflow": float(overflow),
    }


@dataclasses.dataclass
class Verdict:
    """What the check found over every batch it compared."""

    limits: Dict[str, float]
    worst: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in NAMES})
    attempted: int = 0
    failed: int = 0
    per_batch: List[Dict[str, float]] = dataclasses.field(default_factory=list)

    def add(self, numbers: Dict[str, float]) -> bool:
        """Record one batch; returns whether it passed."""
        self.attempted += 1
        self.per_batch.append(numbers)
        ok = True
        for k in NAMES:
            self.worst[k] = max(self.worst[k], numbers[k])
            if not numbers[k] <= self.limits[k]:
                ok = False
        if not ok:
            self.failed += 1
        return ok

    @property
    def correct(self) -> bool:
        return self.attempted > 0 and self.failed == 0

    def lines(self) -> List[str]:
        """One line per number, with its limit."""
        return [f"check {k} {self.worst[k]!r} limit {self.limits[k]!r}" for k in NAMES]

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": self.worst[k], "limit": self.limits[k]} for k in NAMES}


def limits_of(config: dict) -> Dict[str, float]:
    lim = config["limits"]
    missing = [k for k in NAMES if lim.get(k) is None]
    if missing:
        raise ValueError(f"configuration {config['name']} sets no limit for {missing}")
    return {k: float(lim[k]) for k in NAMES}
