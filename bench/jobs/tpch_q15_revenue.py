"""TPC-H Q15's revenue view as a MapReduce job.

    SELECT l_suppkey, SUM(l_extendedprice * (1 - l_discount))
    FROM lineitem
    WHERE l_shipdate >= DATE '1996-01-01'
      AND l_shipdate < DATE '1996-01-01' + INTERVAL '3' MONTH
    GROUP BY l_suppkey

The date is Q15's validation parameter. The map evaluates the predicate on
every row and emits it as the pair's valid flag, so the engine reduces only
the rows of the quarter, about 3.8% of the scan. Rows follow the TPC-H
generator (specification 4.2.3) with the skewed generator's Zipf draw of
``l_suppkey`` (Chaudhuri and Narasayya, "TPC-D data generation with
skew"): rank ``r`` of ``suppliers`` has weight ``r^-z``, and the seed's
permutation of the suppliers picks which are hot.

How many rows of each rank fall in each block of ``block_rows`` rows is a
multinomial draw from the fixed ``sizes_seed`` (:func:`size_table`), and
each row's ship date is drawn from it too, in the block's rank-sorted
layout; the seed
orders the rows within each block and draws the other columns. Every run
seed thus has the same valid rows per rank and block, the same statistics
up to which supplier is which, hence the same plan shapes and the same
compiled programs.

- ``l_quantity`` uniform in 1..50;
- ``l_partkey`` uniform in 1..parts, ``p_retailprice`` =
  (90000 + (partkey / 10) mod 20001 + 100 (partkey mod 1000)) / 100;
- ``l_extendedprice`` = ``l_quantity * p_retailprice``, stored as float32
  as the engine holds it;
- ``l_discount`` uniform in 0.00..0.10 by 0.01, stored as float32;
- ``l_shipdate`` = ``o_orderdate`` + 1..121 days, ``o_orderdate`` uniform
  from STARTDATE (1992-01-01) to ENDDATE - 151 days (1998-08-02); stored
  as int32 days since STARTDATE.

The map emits ``(l_suppkey, revenue:(1,) f32, valid)``. Supplier keys are
dense, 1..suppliers, and below the engine's ``num_clusters``, so each
cluster is one key and the engine reduces per key. Rows are drawn on the
device (``make_batch``, traced once for the whole pool); the reference and
the control read host copies of the same rows.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

COLUMNS = ("l_suppkey", "l_extendedprice", "l_discount", "l_shipdate")
VALUE_DIM = 1
# Days since STARTDATE, 1992-01-01.
ORDERDATE_LAST = 2405   # ENDDATE - 151 days, 1998-08-02
SHIP_FROM = 1461        # 1996-01-01
SHIP_TO = 1552          # 1996-04-01, exclusive


def size_table(job: dict, pool_batches: int) -> np.ndarray:
    """Rows of each Zipf rank in each block of each pool batch, ``(batches,
    blocks, suppliers)`` int32: multinomial draws from ``sizes_seed``, the
    same for every run seed, so every seed does the same work."""
    s, block = int(job["suppliers"]), int(job["block_rows"])
    blocks = int(job["rows_per_batch"]) // block
    weights = np.arange(1, s + 1, dtype=np.float64) ** -float(job["zipf_z"])
    rng = np.random.default_rng(int(job["sizes_seed"]))
    return rng.multinomial(block, weights / weights.sum(),
                           size=(pool_batches, blocks)).astype(np.int32)


def make_batch(job: dict, key, hot_key, table, b: int) -> dict:
    """Pool batch ``b``: ``rows_per_batch`` rows as flat device columns (traced).

    ``table`` is :func:`size_table`. ``hot_key`` draws the permutation of
    the suppliers that picks which are hot; ``key`` the order of the rows
    in each block and the other columns. Ship dates are drawn from
    ``sizes_seed`` in the rank-sorted layout, then move with their rows.
    """
    import jax
    import jax.numpy as jnp

    n, s = int(job["rows_per_batch"]), int(job["suppliers"])
    sizes = table[b]
    blocks, block = sizes.shape[0], int(job["block_rows"])
    k_order, k_part, k_qty, k_disc = jax.random.split(key, 4)
    k_odate, k_lag = jax.random.split(
        jax.random.fold_in(jax.random.key(int(job["sizes_seed"])), b))
    hot = jax.random.permutation(hot_key, s).astype(jnp.int32) + 1
    ends = jnp.cumsum(sizes, axis=1)
    rows = jnp.arange(block, dtype=jnp.int32)
    ranks = jax.vmap(lambda e: jnp.searchsorted(e, rows, side="right"))(ends)
    shipdate = (jax.random.randint(k_odate, (blocks, block), 0, ORDERDATE_LAST + 1)
                + jax.random.randint(k_lag, (blocks, block), 1, 122))
    order = jax.vmap(lambda k: jax.random.permutation(k, block))(
        jax.random.split(k_order, blocks))
    ranks = jnp.take_along_axis(ranks, order, axis=1).reshape(n)
    shipdate = jnp.take_along_axis(shipdate, order, axis=1).reshape(n)
    partkey = jax.random.randint(k_part, (n,), 1, int(job["parts"]) + 1)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    quantity = jax.random.randint(k_qty, (n,), 1, 51)
    discount = jax.random.randint(k_disc, (n,), 0, 11)
    return {
        "l_suppkey": hot[ranks],
        "l_extendedprice": (quantity * retail_cents).astype(jnp.float32) / 100.0,
        "l_discount": discount.astype(jnp.float32) / 100.0,
        "l_shipdate": shipdate.astype(jnp.int32),
    }


def map_fn(shard):
    """The job's Map, run by the engine on the device: ``(key, (1,) value, valid)``."""
    key = shard["l_suppkey"]
    revenue = shard["l_extendedprice"] * (1.0 - shard["l_discount"])
    ship = shard["l_shipdate"]
    return key, revenue[..., None], (ship >= SHIP_FROM) & (ship < SHIP_TO)


def valid(batch: dict) -> np.ndarray:
    """Q15's date predicate on every row, shard-major like the batch."""
    ship = batch["l_shipdate"]
    return (ship >= SHIP_FROM) & (ship < SHIP_TO)


def group_ids(batch: dict) -> np.ndarray:
    """The GROUP BY key of every row (the output row it lands in), valid or not."""
    return batch["l_suppkey"].astype(np.int64)


def _sums(batch: dict, revenue: np.ndarray, num_groups: int):
    ok = valid(batch).reshape(-1)
    g = group_ids(batch).reshape(-1)[ok]
    values = np.bincount(g, weights=revenue.reshape(-1)[ok], minlength=num_groups)[:, None]
    counts = np.bincount(g, minlength=num_groups).astype(np.float64)
    return values, counts


def reference(batch: dict, num_groups: int):
    """Plain float64 Q15 revenue view: ``(values (n, 1), counts (n,))``."""
    revenue = (batch["l_extendedprice"].astype(np.float64)
               * (1.0 - batch["l_discount"].astype(np.float64)))
    return _sums(batch, revenue, num_groups)


def control(batch: dict, num_groups: int):
    """The reference one precision step below float32: each row's revenue in
    bfloat16, the sums kept exact. The mildest bfloat16 path a change could
    take (a bf16 map or wire), so the check has to reject it."""
    bf16 = ml_dtypes.bfloat16
    price = batch["l_extendedprice"].astype(bf16)
    keep = (np.ones(price.shape, bf16) - batch["l_discount"].astype(bf16)).astype(bf16)
    revenue = (price * keep).astype(bf16).astype(np.float64)
    values, counts = _sums(batch, revenue, num_groups)
    return values.astype(np.float32), counts.astype(np.float32)
