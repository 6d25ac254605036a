"""Closed-loop traffic from a pool of batches drawn from the seed.

One batch is in flight: the next is submitted once the last one's outputs
are on the host. A mix of this kind is a JSON file with:

- ``pool_batches``: batches drawn from the seed, served round robin;
- ``warmup_batches``: batches served in set-up, before the window.

The key distribution is stationary: one draw of the hot keys per run.
Every seed gives the same sizes and the same order of batches; the seed
changes the rows and which keys are hot (see the job's ``size_table``).
"""

from __future__ import annotations


def check(params: dict) -> None:
    """Refuse parameters this generator cannot serve."""
    if int(params["pool_batches"]) < 1 or int(params["warmup_batches"]) < 1:
        raise ValueError("closed_pool needs pool_batches >= 1 and warmup_batches >= 1")


def seed_key(seed: int):
    """A JAX key from any whole number: its low 32 bits, then the rest folded in."""
    import jax

    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def make_pool(params: dict, job_module, job: dict, seed: int, shape, sharding) -> list:
    """The pool's batches, drawn on the device in one jitted call.

    Each batch is a dict of columns reshaped to ``shape`` (shards, rows per
    shard) and placed by ``sharding``. The seed's key and the job's size
    table are arguments, so one compiled program serves every seed.
    """
    import jax

    check(params)
    pool = int(params["pool_batches"])

    def draw(base, table):
        hot_key = jax.random.fold_in(base, 0x5EED)
        out = []
        for b in range(pool):
            batch = job_module.make_batch(job, jax.random.fold_in(base, b), hot_key, table, b)
            out.append({c: v.reshape(shape) for c, v in batch.items()})
        return out

    return jax.jit(draw, out_shardings=sharding)(seed_key(seed),
                                                  job_module.size_table(job, pool))


def pool_index(params: dict, i: int) -> int:
    """Pool batch served as the ``i``-th batch of the run (warm-up included)."""
    return i % int(params["pool_batches"])
