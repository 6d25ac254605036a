"""The spill: counting sort of pairs into fixed-capacity group buffers.

``_ragged_counting_sort_to_buckets`` against a numpy reference that
stable-sorts the pairs by group and keeps each group's first ``cap``
pairs, and a guard that the spill's lowered program holds no loop over
the pairs.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.mapreduce import (
    _counting_sort_to_buckets,
    _ragged_counting_sort_to_buckets,
)

V = 3


def _reference(group, values, payload, caps):
    """Stable sort by group; group g's first caps[g] pairs fill its slab."""
    num_groups = len(caps)
    total = int(caps.sum())
    base = np.concatenate([[0], np.cumsum(caps)[:-1]])
    bv = np.zeros((total, values.shape[1]), values.dtype)
    bc = np.full(total, -1, np.int32)
    bm = np.zeros(total, bool)
    overflow = 0
    for g in range(num_groups):
        members = np.flatnonzero(group == g)  # index order = stable order
        kept = members[: caps[g]]
        overflow += len(members) - len(kept)
        rows = base[g] + np.arange(len(kept))
        bv[rows] = values[kept]
        bc[rows] = payload[kept]
        bm[rows] = True
    return bv, bc, bm, overflow


def _pairs(rng, k, group_ids):
    group = rng.choice(np.asarray(group_ids, np.int32), size=k).astype(np.int32)
    values = rng.integers(1, 100, size=(k, V)).astype(np.float32)
    payload = rng.integers(0, 1000, size=k).astype(np.int32)
    return group, values, payload


# (caps, group ids the pairs draw from; len(caps) is the invalid id)
CASES = {
    "ragged_with_zero_cap": ([3, 0, 5, 2, 7], [0, 1, 2, 3, 4, 5]),
    "empty_groups": ([4, 4, 4, 4], [0, 2, 4]),
    "all_invalid": ([2, 3, 1], [3]),
    "over_capacity": ([1, 2, 1], [0, 1, 2]),
    "roomy": ([40, 40], [0, 1, 2]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_spill_matches_stable_sort(case):
    caps_list, ids = CASES[case]
    caps = np.asarray(caps_list, np.int64)
    rng = np.random.default_rng(len(case))
    group, values, payload = _pairs(rng, 48, ids)
    got = _ragged_counting_sort_to_buckets(
        jnp.asarray(group), jnp.asarray(values), jnp.asarray(payload),
        caps, int(caps.sum()),
    )
    want = _reference(group, values, payload, caps)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert int(got[3]) == want[3]
    if case == "over_capacity":
        assert want[3] > 0
    if case == "all_invalid":
        assert want[3] == 0 and not want[2].any()


@pytest.mark.parametrize("capacity", [0, 2, 5, 64])
def test_uniform_spill_matches_stable_sort(capacity):
    slots = 4
    rng = np.random.default_rng(capacity)
    dest, values, payload = _pairs(rng, 40, range(slots + 1))
    bv, bc, bm, overflow = _counting_sort_to_buckets(
        jnp.asarray(dest), jnp.asarray(values), jnp.asarray(payload),
        slots, capacity,
    )
    caps = np.full(slots, capacity, np.int64)
    wv, wc, wm, wo = _reference(dest, values, payload, caps)
    np.testing.assert_array_equal(np.asarray(bv), wv.reshape(slots, capacity, V))
    np.testing.assert_array_equal(np.asarray(bc), wc.reshape(slots, capacity))
    np.testing.assert_array_equal(np.asarray(bm), wm.reshape(slots, capacity))
    assert int(overflow) == wo


@pytest.mark.parametrize("caps_list", [[6, 0, 9, 3], [1, 1, 1, 1]])
def test_vmapped_spill_matches_per_lane(caps_list):
    lanes, k = 8, 64
    caps = np.asarray(caps_list, np.int64)
    rng = np.random.default_rng(sum(caps_list))
    drawn = [_pairs(rng, k, range(len(caps) + 1)) for _ in range(lanes)]
    group, values, payload = (np.stack(a) for a in zip(*drawn))
    spill = jax.vmap(
        lambda g, v, p: _ragged_counting_sort_to_buckets(
            g, v, p, caps, int(caps.sum())
        )
    )
    got = spill(jnp.asarray(group), jnp.asarray(values), jnp.asarray(payload))
    for lane in range(lanes):
        want = _reference(group[lane], values[lane], payload[lane], caps)
        for g, w in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(np.asarray(g[lane]), w)
        assert int(got[3][lane]) == want[3]


# ---------------------------------------------------------------------------
# No loop over the pairs: a per-pair binary search is a `while` whose
# operands include a K-long array.
K_GUARD = 1 << 16


def _while_lines_with_k_operand(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    k_shape = re.compile(rf"[<x]{K_GUARD}[x>]")
    return [ln for ln in text.splitlines()
            if "stablehlo.while" in ln and k_shape.search(ln)]


def _guard_args():
    group = jax.ShapeDtypeStruct((K_GUARD,), jnp.int32)
    values = jax.ShapeDtypeStruct((K_GUARD, V), jnp.float32)
    payload = jax.ShapeDtypeStruct((K_GUARD,), jnp.int32)
    return group, values, payload


def test_guard_sees_a_per_pair_search():
    group, _, _ = _guard_args()
    found = _while_lines_with_k_operand(
        lambda g: jnp.searchsorted(g, g, side="left"), group
    )
    assert found


@pytest.mark.parametrize("num_groups", [32, 16, 256])
def test_spill_has_no_loop_over_pairs(num_groups):
    caps = np.full(num_groups, K_GUARD // num_groups, np.int64)
    found = _while_lines_with_k_operand(
        lambda g, v, p: _ragged_counting_sort_to_buckets(
            g, v, p, caps, int(caps.sum())
        ),
        *_guard_args(),
    )
    assert not found, found[:1]
