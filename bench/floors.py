"""The least time phase B could take on the chip, counted from a job's shapes.

Phase B (spill, copy, reduce) has to move every valid pair at least once;
whatever implements it, it cannot beat either floor:

- HBM: each chip reads each of its valid pairs once (a 4-byte key and
  ``V`` 4-byte values) and writes each of its reducers' ``(n, V)`` float32
  outputs, at the chip's published HBM rate;
- ICI (more than one chip): every pair whose group has more pairs on
  another chip has to leave its chip. The fewest that must leave is, per
  group, its pairs less those on the chip that holds most of them; those
  bytes cross at the published per-chip ICI rate, all chips sending at once.
  The count does not depend on how groups are labelled, so it is made over
  the groups' dense ranks: a hashed 31-bit group id costs no more than a
  small one.

The floor is the larger of the two; ``bound`` names it.
"""

from __future__ import annotations

import numpy as np


def pair_bytes(value_dim: int) -> int:
    return 4 + 4 * value_dim


def phase_b_floor(groups: np.ndarray, valid: np.ndarray, *, num_shards: int,
                  num_groups: int, value_dim: int, num_reducers: int, chips: int,
                  peaks: dict) -> dict:
    """Floors of one batch whose ``groups`` lie shard-major, ``(num_shards, K)``.

    Only rows whose ``valid`` is set count as pairs. Returns seconds:
    ``hbm_s``, ``ici_s``, ``floor_s`` and the name of the floor that bounds
    it, ``bound``.
    """
    groups = np.asarray(groups).reshape(num_shards, -1)
    valid = np.asarray(valid, bool).reshape(groups.shape)
    per_chip_pairs = int(valid.sum()) / chips
    per_chip_out = (num_reducers / chips) * num_groups * value_dim * 4
    hbm_s = (per_chip_pairs * pair_bytes(value_dim) + per_chip_out) / peaks["hbm_bytes_per_s"]
    ici_s = 0.0
    if chips > 1:
        chip_of_shard = np.arange(num_shards) * chips // num_shards
        chip = np.broadcast_to(chip_of_shard[:, None], groups.shape)[valid]
        present, rank = np.unique(groups[valid], return_inverse=True)
        per_chip = np.bincount(chip * present.size + rank.reshape(-1),
                               minlength=chips * present.size)
        per_chip = per_chip.reshape(chips, present.size)
        leaving = int(per_chip.sum() - per_chip.max(axis=0).sum())
        ici_s = leaving * pair_bytes(value_dim) / (chips * peaks["ici_bytes_per_s"])
    floor = max(hbm_s, ici_s)
    return {"hbm_s": hbm_s, "ici_s": ici_s, "floor_s": floor,
            "bound": "ici" if ici_s > hbm_s else "hbm"}
