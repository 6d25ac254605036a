"""Balanced Subset Sum (BSS) — the per-slot sub-problem of the paper's scheduler.

The paper (§4.2, and the companion manuscript [F+14] arXiv:1401.0355) reduces
``P||C_max`` to a sequence of *Balanced Subset Sum* problems via dynamic
programming decomposition: for each slot in turn, select a subset of the
remaining operations whose total load is as close as possible to the balanced
target ``T = remaining_total / remaining_slots``.

We provide:

* :func:`bss_exact` — exact DP over achievable sums (weakly NP-hard /
  pseudo-polynomial), for small integer instances and as the test oracle.
* :func:`bss_approx` — FPTAS-style grid DP with relative error ``<= eta``,
  implemented with Python big-int bitsets so a 480-operation, ``eta=0.002``
  instance solves in milliseconds (paper Fig 10: < 0.5 s end to end).

Both return the *indices* of the chosen subset.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

__all__ = ["bss_exact", "bss_approx", "subset_closest_to_target"]


def _reconstruct(units: Sequence[int], snapshots: List[int], g: int) -> List[int]:
    """Walk the per-item reachability snapshots backwards to recover a subset.

    ``snapshots[i]`` is the reachability bitset *after* considering items
    ``0..i-1`` (so ``snapshots[0] == 1``, only sum 0 reachable).
    """
    chosen: List[int] = []
    for i in range(len(units) - 1, -1, -1):
        before = snapshots[i]
        if (before >> g) & 1:
            # ``g`` was already reachable without item i — skip it.
            continue
        # Item i must be part of the subset.
        chosen.append(i)
        g -= units[i]
        assert g >= 0, "BSS reconstruction walked below zero"
    chosen.reverse()
    return chosen


def _bitset_dp(units: Sequence[int], bound: int) -> Tuple[int, List[int]]:
    """0/1 subset-sum reachability over ``[0, bound]`` with big-int bitsets.

    Returns ``(final_bitset, snapshots)`` where snapshots[i] is the bitset
    before item ``i`` was applied.
    """
    mask = (1 << (bound + 1)) - 1
    reach = 1  # only the empty sum
    snapshots: List[int] = []
    for u in units:
        snapshots.append(reach)
        if u <= bound:
            reach |= (reach << u) & mask
    return reach, snapshots


def _closest_bit(reach: int, target: int, bound: int) -> int:
    """Index of the set bit in ``reach`` closest to ``target`` (ties: lower)."""
    # One O(bits) conversion, then an outward scan over a flat string —
    # avoids O(bits) big-int shifts per probe.
    bits = bin(reach)[2:][::-1]  # bits[i] == '1'  <=>  sum i reachable
    n = len(bits)
    target = min(target, bound)
    for dist in range(0, bound + 1):
        lo = target - dist
        hi = target + dist
        if 0 <= lo < n and bits[lo] == "1":
            return lo
        if lo < 0 and hi >= n:
            break
        if hi < n and bits[hi] == "1":
            return hi
    # Sum 0 (empty subset) is always reachable.
    return 0


def subset_closest_to_target(
    units: Sequence[int], target: int, bound: int | None = None
) -> List[int]:
    """Exact: subset of ``units`` whose sum is closest to ``target``.

    ``bound`` caps the DP table (defaults to a small overshoot above target —
    any sum further above the target than the largest single item can never
    be closest).
    """
    if not units:
        return []
    if bound is None:
        bound = target + max(units)
    bound = max(bound, 1)
    reach, snaps = _bitset_dp(units, bound)
    g = _closest_bit(reach, min(target, bound), bound)
    return _reconstruct(units, snaps, g)


def bss_exact(loads: Sequence[float], target: float) -> List[int]:
    """Exact BSS for integer-ish loads (test oracle; pseudo-polynomial)."""
    units = [int(round(x)) for x in loads]
    if any(u < 0 for u in units):
        raise ValueError("loads must be non-negative")
    return subset_closest_to_target(units, int(round(target)))


def bss_approx(loads: Sequence[float], target: float, eta: float = 0.002) -> List[int]:
    """FPTAS-style BSS: subset with ``|sum - target| <= eta * target`` of optimal.

    Loads are rounded down onto a grid of ``delta = eta * target / k`` so the
    accumulated rounding error over at most ``k`` chosen items is bounded by
    ``eta * target``. The DP is a big-int bitset shift-or, O(k) shifts of a
    ``O(k/eta)``-bit integer, and keeps one snapshot per item: O(k²/eta)
    bits. Past :data:`DP_BITS_BUDGET` (thousands of operations) that is
    hundreds of GiB, so :func:`_bss_split` solves the instance instead,
    within the same ``eta * target``.
    """
    k = len(loads)
    if k == 0:
        return []
    if target <= 0:
        return []
    if eta <= 0:
        return bss_exact(loads, target)
    delta = (eta * target) / k
    if delta <= 0:
        delta = 1.0
    units = [int(x / delta) for x in loads]
    tgt = int(target / delta)
    # Allow a modest overshoot window: a sum slightly above target can still
    # be the closest achievable one.
    bound = tgt + max(max(units), 1)
    if k * bound > DP_BITS_BUDGET:
        return _bss_split(loads, target, eta)
    reach, snaps = _bitset_dp(units, bound)
    g = _closest_bit(reach, tgt, bound)
    return _reconstruct(units, snaps, g)


# Snapshot bits the one-grid DP may keep (2^31 bits = 256 MiB).
DP_BITS_BUDGET = 1 << 31


def _bss_split(loads: Sequence[float], target: float, eta: float) -> List[int]:
    """BSS for many operations: DP over the large ones, greedy fill with the small.

    An operation is small when its load is at most ``theta = eta * target
    / 2``. The large ones are rounded onto a grid of ``theta / c``, where
    ``c`` bounds how many of them fit under the DP's sum bound, so their
    rounding costs less than ``theta``. The DP picks the large subset whose
    sum lies closest to ``[target - small_total, target]``; the small
    operations, heaviest first, then join while they fit under ``target``,
    which ends less than ``theta`` short whenever enough small load
    exists. Total error: below ``eta * target`` of the best subset. Under
    skew the large operations are few (a Zipf(1.1) batch over 65,536
    clusters has about 550), so the DP stays small.
    """
    theta = eta * target / 2
    large = [i for i, x in enumerate(loads) if x > theta]
    small = sorted((i for i, x in enumerate(loads) if x <= theta),
                   key=lambda i: -loads[i])
    small_total = sum(loads[i] for i in small)
    chosen: List[int] = []
    total = 0.0
    if large:
        top = max(loads[i] for i in large)
        c = max(1, min(len(large), int((target + top) / theta)))
        delta = theta / c
        units = [int(loads[i] / delta) for i in large]
        hi = int(target / delta)
        lo = int((target - small_total) / delta)
        bound = hi + max(max(units), 1)
        reach, snaps = _bitset_dp(units, bound)
        bits = bin(reach)[2:][::-1]
        below = bits.rfind("1", 0, hi + 1)  # sum 0 is always reachable
        above = bits.find("1", hi + 1)
        g = below
        if below < lo and above >= 0 and above - hi < lo - below:
            g = above
        chosen = [large[j] for j in _reconstruct(units, snaps, g)]
        total = sum(loads[i] for i in chosen)
    for i in small:
        if total + loads[i] <= target:
            chosen.append(i)
            total += loads[i]
    return sorted(chosen)
