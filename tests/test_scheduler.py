"""P||C_max scheduler unit + property tests (paper §3.2/§4.2)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bss, scheduler as S

loads_strategy = st.lists(
    st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
              allow_infinity=False),
    min_size=1, max_size=64)


@given(loads_strategy, st.integers(1, 16))
@settings(max_examples=200, deadline=None)
def test_every_scheduler_assigns_every_operation(loads, m):
    loads = np.asarray(loads)
    for name in ["hash", "lpt", "multifit", "bss"]:
        sched = S.get_scheduler(name)(loads, m) if name != "hash" \
            else S.schedule_hash(loads, m)
        assert sched.assignment.shape == (len(loads),)
        assert ((sched.assignment >= 0) & (sched.assignment < m)).all()
        # conservation: slot loads sum to total load
        assert np.isclose(sched.slot_loads.sum(), loads.sum())


@given(loads_strategy, st.integers(1, 16))
@settings(max_examples=200, deadline=None)
def test_max_load_at_least_ideal_and_biggest(loads, m):
    loads = np.asarray(loads)
    for name in ["lpt", "multifit", "bss"]:
        sched = S.get_scheduler(name)(loads, m)
        assert sched.max_load >= loads.sum() / m - 1e-6
        assert sched.max_load >= loads.max() - 1e-6


@given(st.lists(st.floats(0.0, 1e6, allow_nan=False, allow_infinity=False),
                min_size=1, max_size=10),
       st.integers(1, 4))
@settings(max_examples=60, deadline=None)
def test_lpt_graham_bound(loads, m):
    """LPT is a (4/3 − 1/3m)-approximation of the true optimum [Gr69]."""
    loads = np.asarray(loads)
    opt = S.schedule_brute(loads, m).max_load
    sched = S.schedule_lpt(loads, m)
    assert sched.max_load <= (4 / 3 - 1 / (3 * m)) * opt + 1e-6


@given(st.lists(st.integers(1, 50), min_size=2, max_size=10),
       st.integers(2, 4))
@settings(max_examples=60, deadline=None)
def test_bss_close_to_brute_force(loads, m):
    """The paper's near-optimality claim on exhaustive tiny instances."""
    loads = np.asarray(loads, dtype=float)
    opt = S.schedule_brute(loads, m)
    got = S.schedule_bss(loads, m, eta=0.002)
    # eta=0.002 => within 0.2% of optimal, paper §5 point 5 (+tiny slack
    # for the greedy last-slot remainder).
    assert got.max_load <= opt.max_load * 1.35 + 1e-6
    # and never worse than plain LPT
    assert got.max_load <= S.schedule_lpt(loads, m).max_load + 1e-6


def test_bss_beats_hash_on_skew(rng):
    loads = rng.zipf(1.3, 480).astype(float)
    hash_s = S.schedule_hash(loads, 30, keys=np.arange(480))
    bss_s = S.schedule_bss(loads, 30)
    assert bss_s.balance_ratio <= hash_s.balance_ratio
    # Fig 6: OS4M max-load/ideal close to 1 when no single op dominates
    if loads.max() < loads.sum() / 30:
        assert bss_s.balance_ratio < 1.2


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=30),
       st.integers(1, 2000))
@settings(max_examples=100, deadline=None)
def test_bss_exact_subset_closest(units, target):
    """Exact BSS: no other subset is closer to the target."""
    got = bss.subset_closest_to_target(units, target)
    sum_got = sum(units[i] for i in got)
    # exhaustive check on small instances only
    if len(units) <= 12:
        best = min(
            (abs(sum(units[i] for i in range(len(units)) if (mask >> i) & 1)
                 - target)
             for mask in range(1 << len(units))))
        assert abs(sum_got - target) == best


@given(st.lists(st.floats(0.0, 1e4, allow_nan=False), min_size=1,
                max_size=100),
       st.floats(1.0, 1e5), st.floats(0.001, 0.1))
@settings(max_examples=100, deadline=None)
def test_bss_approx_indices_valid(loads, target, eta):
    got = bss.bss_approx(loads, target, eta=eta)
    assert len(set(got)) == len(got)
    assert all(0 <= i < len(loads) for i in got)


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=12),
       st.integers(1, 4000), st.floats(0.01, 0.5))
@settings(max_examples=100, deadline=None)
def test_bss_split_within_eta_of_best_subset(units, target, eta):
    """The many-operation path keeps the FPTAS bound: eta * target."""
    loads = [float(u) for u in units]
    got = bss._bss_split(loads, float(target), eta)
    assert len(set(got)) == len(got)
    best = min(abs(sum(u for i, u in enumerate(units) if (mask >> i) & 1) - target)
               for mask in range(1 << len(units)))
    assert abs(sum(loads[i] for i in got) - target) <= best + eta * target + 1e-9


def test_bss_at_real_cluster_count(rng):
    """65,536 Zipf-loaded clusters on 8 slots: the one-grid DP would keep
    ~500 GiB of snapshots; the split path plans in about a second."""
    loads = np.bincount(rng.zipf(1.1, 1 << 20) % 65536, minlength=65536).astype(float)
    target = loads.sum() / 8
    assert len(loads) * len(loads) / 0.002 > bss.DP_BITS_BUDGET
    chosen = bss.bss_approx(loads.tolist(), target, eta=0.002)
    assert abs(loads[chosen].sum() - target) <= 0.002 * target
    sched = S.schedule_bss(loads, 8)
    assert sched.max_load <= S.schedule_lpt(loads, 8).max_load + 1e-6


def test_lpt_assign_jax_matches_host():
    import jax.numpy as jnp

    loads = np.asarray([5, 3, 8, 1, 9, 2, 7, 4], float)
    assign, slot_loads = S.lpt_assign_jax(jnp.asarray(loads), 3)
    host = S.schedule_lpt(loads, 3)
    got = np.bincount(np.asarray(assign), weights=loads, minlength=3)
    assert np.isclose(sorted(got)[-1], host.max_load)


# ---------------------------------------------------------------------------
# schedule_os4m: LPT where the lower bound certifies it, else BSS.
# ---------------------------------------------------------------------------

SPEEDS = {"uniform": None, "uneven": (1.0, 0.5, 2.0, 1.5), "dead": (1.0, 0.0, 2.0, 1.0)}


def _os4m_is_certified_lpt_or_bss(loads, m, speeds, eta=0.002) -> bool:
    """``schedule_os4m`` is LPT's schedule, within ``1 + eta`` of the lower
    bound, where that bound certifies LPT, and ``schedule_bss``'s otherwise;
    returns whether LPT was certified."""
    got = S.schedule_os4m(loads, m, eta=eta, speeds=speeds)
    lpt = S.schedule_lpt(loads, m, speeds=speeds)
    lb = S.makespan_lower_bound(loads, m, speeds)
    certified = lpt.makespan <= (1 + eta) * lb
    want = lpt if certified else S.schedule_bss(loads, m, eta=eta, speeds=speeds)
    np.testing.assert_array_equal(got.assignment, want.assignment)
    if certified:
        assert got.makespan <= (1 + eta) * lb
    assert got.makespan <= lpt.makespan
    if speeds is not None:
        assert not got.slot_loads[np.asarray(speeds) == 0].any()
    return certified


def _q15_loads(seed: int) -> np.ndarray:
    """Loads shaped like TPC-H Q15's: 158,500 valid pairs, Zipf z = 1 over
    10,000 suppliers, one supplier a cluster of 16,384."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, 10_001)
    suppliers = rng.permutation(10_000)[rng.choice(10_000, 158_500, p=p / p.sum())]
    return np.bincount(suppliers, minlength=16_384).astype(float)


@pytest.mark.parametrize("kind", sorted(SPEEDS))
@given(st.lists(st.integers(1, 50), min_size=2, max_size=10),
       st.integers(2, 4))
@settings(max_examples=60, deadline=None)
def test_os4m_lower_bound_and_result_on_brute_force_instances(kind, loads, m):
    """The bound never passes the exact optimum; the result is certified
    LPT or BSS, and never worse than LPT."""
    loads = np.asarray(loads, dtype=float)
    speeds = None if SPEEDS[kind] is None else SPEEDS[kind][:m]
    opt = S.schedule_brute(loads, m, speeds=speeds).makespan
    assert S.makespan_lower_bound(loads, m, speeds) <= opt * (1 + 1e-12)
    _os4m_is_certified_lpt_or_bss(loads, m, speeds)


@pytest.mark.parametrize("loads, m, speeds, certified", [
    ([3, 3, 2, 2, 2], 2, None, False),                # LPT 7, bound 6
    ([3, 3, 2, 2, 2], 3, (1.0, 0.0, 1.0), False),     # the same on the alive slots
    ([7, 6, 5, 4, 4, 4], 2, (1.5, 1.0), False),       # LPT 13.33, bound 12
    ([4, 2, 2], 3, (2.0, 1.0, 1.0), True),            # LPT at the bound, 2
    ([1] * 12, 4, (1.0, 0.0, 1.0, 1.0), True),        # 4 on each alive slot
])
def test_os4m_small_instances(loads, m, speeds, certified):
    assert _os4m_is_certified_lpt_or_bss(np.asarray(loads, float), m, speeds) is certified


@pytest.mark.parametrize("m", [8, 4])
def test_os4m_certifies_q15_shaped_loads(m):
    """On Q15's loads LPT is certified, and as good as the BSS DP."""
    loads = _q15_loads(0)
    assert _os4m_is_certified_lpt_or_bss(loads, m, None)
    got = S.schedule_os4m(loads, m)
    assert got.finish_ratio == S.schedule_bss(loads, m).finish_ratio
    assert S.get_scheduler("os4m") is S.schedule_os4m
    assert S.get_scheduler("bss") is S.schedule_bss
