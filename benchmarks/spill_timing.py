"""Standalone timings of the phase-B spill in three forms, on one device.

    PYTHONPATH=src python -m benchmarks.spill_timing [--out FILE]

Times ``_ragged_counting_sort_to_buckets`` (the engine's spill: one stable
sort of ``(group, index)``, then each bucket row gathers its own pair)
against two other forms that give the same buckets:

* ``carry``: the values and payload ride through the sort as operands, and
  the bucket rows gather from the sorted arrays;
* ``scatter``: the spill as it was before the gather form (an argsort,
  K-wide gathers into sorted order, K-wide scatters into the buckets);

and the sort alone. The shapes are the benchmark cells' (Q15 on one chip
under vmap and on four chips, one shard a chip; WordCount after its
combiner) and one where every cap is K, so the rows outnumber the pairs
16 times. Groups are drawn in proportion to the caps, with Q15's share of
valid pairs. Each form is checked bit for bit against ``scatter``. Prints
one JSON line a shape; ``--out`` writes them all as one JSON file.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mapreduce import _ragged_counting_sort_to_buckets


def _bases(group_caps: np.ndarray) -> np.ndarray:
    base = np.zeros(group_caps.shape[0], np.int64)
    base[1:] = np.cumsum(group_caps)[:-1]
    return base


def _starts(g_sorted, num_groups: int):
    return jnp.searchsorted(
        g_sorted, jnp.arange(num_groups + 1, dtype=g_sorted.dtype),
        side="left", method="compare_all",
    )


def carry_form(group, values, payload, group_caps, total):
    """The gather form, with values and payload sorted beside the key."""
    num_groups = group_caps.shape[0]
    cols = [values[:, j] for j in range(values.shape[-1])]
    g_sorted, p_sorted, *cols = jax.lax.sort(
        (group, payload, *cols), num_keys=1, is_stable=True)
    starts = _starts(g_sorted, num_groups)
    counts = starts[1:] - starts[:-1]
    overflow = jnp.sum(jnp.maximum(counts - jnp.asarray(group_caps, jnp.int32), 0))
    base = jnp.asarray(_bases(group_caps), jnp.int32)
    row = jnp.arange(total, dtype=jnp.int32)
    g_of_row = jnp.searchsorted(base, row, side="right", method="compare_all") - 1
    pos = row - base[g_of_row]
    ok = pos < counts[g_of_row]
    src = jnp.where(ok, starts[g_of_row] + pos, 0)
    bucket_values = jnp.where(ok[:, None], jnp.stack([c[src] for c in cols], -1), 0)
    return bucket_values, jnp.where(ok, p_sorted[src], -1), ok, overflow


def scatter_form(group, values, payload, group_caps, total):
    """The spill before the gather form: K-wide gathers and scatters."""
    k = group.shape[0]
    num_groups = group_caps.shape[0]
    order = jnp.argsort(group, stable=True)
    g_sorted = group[order]
    pos = jnp.arange(k) - _starts(g_sorted, num_groups)[g_sorted]
    g_clip = jnp.clip(g_sorted, 0, num_groups - 1)
    cap_of = jnp.asarray(group_caps, jnp.int32)[g_clip]
    in_range = g_sorted < num_groups
    ok = in_range & (pos < cap_of)
    overflow = jnp.sum(in_range & (pos >= cap_of))
    flat = jnp.where(ok, jnp.asarray(_bases(group_caps), jnp.int32)[g_clip] + pos,
                     total)

    def to_rows(fill, x):
        return jnp.full((total + 1,) + x.shape[1:], fill, x.dtype).at[flat].set(x)[:-1]

    return (to_rows(0, jnp.where(ok[:, None], values[order], 0)),
            to_rows(-1, jnp.where(ok, payload[order], -1)),
            to_rows(False, ok), overflow)


def sort_only(group, values, payload, group_caps, total):
    g_sorted, order = jax.lax.sort(
        (group, jnp.arange(group.shape[0], dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    return g_sorted[:1], order[:1]


FORMS = {"gather": _ragged_counting_sort_to_buckets, "carry": carry_form,
         "scatter": scatter_form, "sort_only": sort_only}

# name: (vmap lanes, 0 for none; K; V; caps of each chunk a slot; slots;
#        share of valid pairs)
SHAPES = {
    "q15_1chip_8x2^19": (8, 1 << 19, 1, (3584, 896, 1792, 768), 8, 0.0378),
    "q15_4chip_1x2^20": (0, 1 << 20, 1, (14336, 3584, 7168, 3072), 4, 0.0378),
    "caps_at_K_1x2^18": (0, 1 << 18, 1, (1 << 18,) * 4, 4, 1.0),
    "wordcount_8x1024": (8, 1024, 2, (44, 44, 44, 44), 8, 0.98),
}


def _draw_groups(rng, k: int, caps: np.ndarray, valid_share: float) -> np.ndarray:
    group = rng.choice(len(caps), size=k, p=caps / caps.sum()).astype(np.int32)
    group[rng.random(k) >= valid_share] = len(caps)
    return group


def _time_ms(fn, args, warm: int, repeats: int) -> dict:
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    for _ in range(warm):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    q1, median, q3 = np.quantile(times, [0.25, 0.5, 0.75])
    return {"out": out, "first_s": first_s, "ms": float(median),
            "ms_q1_q3": [float(q1), float(q3)]}


def time_shape(rng, lanes, k, v, chunk_caps, slots, valid_share,
               warm: int, repeats: int) -> dict:
    caps = np.repeat(np.asarray(chunk_caps, np.int64), slots)
    total = int(caps.sum())
    n = max(lanes, 1)
    group = np.stack([_draw_groups(rng, k, caps, valid_share) for _ in range(n)])
    values = rng.integers(1, 1000, size=(n, k, v)).astype(np.float32)
    payload = rng.integers(-2**31, 2**31, size=(n, k), dtype=np.int64).astype(np.int32)
    if not lanes:
        group, values, payload = group[0], values[0], payload[0]
    args = [jax.device_put(a) for a in (group, values, payload)]
    res = {"total": total, "K": k, "lanes": lanes}
    outs = {}
    for name, form in FORMS.items():
        fn = lambda g, x, p, form=form: form(g, x, p, caps, total)  # noqa: E731
        fn = jax.jit(jax.vmap(fn) if lanes else fn)
        timed = _time_ms(fn, args, warm, repeats)
        outs[name] = [np.asarray(x) for x in timed.pop("out")]
        res.update({f"{name}_{key}": val for key, val in timed.items()})
    ref = outs["scatter"]
    for name in ("gather", "carry"):
        res[f"{name}_bit_identical"] = all(
            np.array_equal(a, b) for a, b in zip(outs[name], ref))
    res["overflow"] = int(np.sum(ref[3]))
    res["filled_rows"] = int(np.sum(ref[2]))
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147519001)
    ap.add_argument("--warm", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    report = {"device": jax.devices()[0].device_kind, "seed": args.seed,
              "shapes": {}}
    for name in args.shapes:
        res = time_shape(rng, *SHAPES[name], warm=args.warm, repeats=args.repeats)
        print(name, json.dumps(res), flush=True)
        report["shapes"][name] = res
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
