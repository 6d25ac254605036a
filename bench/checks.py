"""The comparison that decides ``correct``: program outputs against the plain reference.

Both sides are tables of rows named by key, ``(keys, values, counts)``
(:func:`rows`). A job kind's ``reference`` (and ``control``) returns
either that triple or the dense pair ``(values, counts)``, row ``i`` being
key ``i``; the program's output is read the same way, from the job kind's
``outputs(result, num_groups)`` where it defines one, else from the
``JobResult``'s dense ``values`` and ``counts`` (:func:`program_out`).
:func:`compare` aligns two tables on the union of their keys, a row
missing on one side reading as zero, and computes three numbers, each
compared with its own limit from the configuration's ``limits``:

- ``value_rel_err``: the widest gap of an output value from the float64
  reference, over every key of every checked batch, as a share of the
  reference value (of 1 where the reference is 0 or has no row);
- ``count_mismatch``: keys whose pair count differs from the reference
  (counts are exact in float32 below 2^24, so the limit is 0). A key on
  one side only counts, and so does a key named by two rows of one side:
  two keys merged into one row, or one key split over two, is a wrong
  answer;
- ``overflow``: pairs the engine reports as dropped (limit 0).

A batch whose numbers pass a limit counts as failed. A value that is not
finite reads as infinitely far off.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

NAMES = ("value_rel_err", "count_mismatch", "overflow")


def rows(out):
    """One side's output as rows named by key: a triple ``(keys, values,
    counts)`` is that table; a pair ``(values, counts)`` is dense, row ``i``
    being key ``i``."""
    if len(out) == 3:
        return tuple(out)
    values, counts = out
    return np.arange(np.shape(counts)[0]), values, counts


def program_out(job_module, result, num_groups: int):
    """What the program returned for one batch, as the job kind reads a
    ``JobResult``: its ``outputs`` where it defines one, else the dense
    ``(values, counts)``."""
    outputs = getattr(job_module, "outputs", None)
    if outputs is None:
        return result.values, result.counts
    return outputs(result, num_groups)


def _on_union(keys, values, counts, union):
    """One side's values and counts summed onto ``union``, the keys it names
    twice or more, and the keys it has a row for."""
    at = np.searchsorted(union, np.asarray(keys).reshape(-1))
    v = np.zeros((union.size,) + values.shape[1:], np.float64)
    c = np.zeros(union.size, np.float64)
    np.add.at(v, at, values)
    np.add.at(c, at, np.asarray(counts, np.float64).reshape(-1))
    named = np.bincount(at, minlength=union.size)
    return v, c, named > 1, named > 0


def compare(table, ref_table, overflow) -> Dict[str, float]:
    """The compared numbers of one batch: the program's ``table`` against the
    reference's ``ref_table``, each ``(keys, values (R, V), counts (R,))``."""
    keys, values, counts = table
    ref_keys, ref_values, ref_counts = ref_table
    ref_values = np.asarray(ref_values, np.float64)
    values = np.asarray(values, np.float64).reshape((np.size(keys),) + ref_values.shape[1:])
    union = np.union1d(np.asarray(keys).reshape(-1), np.asarray(ref_keys).reshape(-1))
    v, c, dup, has = _on_union(keys, values, counts, union)
    ref_v, ref_c, ref_dup, ref_has = _on_union(ref_keys, ref_values, ref_counts, union)
    gap = np.abs(v - ref_v) / np.maximum(np.abs(ref_v), 1.0)
    rel = float(np.max(gap)) if gap.size else 0.0
    if not np.isfinite(rel):
        rel = float("inf")
    wrong = (c != ref_c) | dup | ref_dup | (has != ref_has)
    return {
        "value_rel_err": rel,
        "count_mismatch": float(np.count_nonzero(wrong)),
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
