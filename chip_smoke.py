"""Chip smoke: the OS4M engine's main path on a TPU at real size.

One PUMA-style word-count job (Zipf alpha=1.1 over 10^6 distinct int32
keys, (8,) f32 small-integer values, 2^20 pairs per Reduce slot) runs
through :class:`repro.core.mapreduce.MapReduceJob` and
:func:`repro.launch.serve.steady_state_loop` with a ``ReusePolicy``, the
way ``python -m repro.launch.serve --steady-state`` drives it: statistics,
the host OS4M schedule, then the pipelined all-to-all shuffle and reduce.
Every batch is checked bit for bit against a plain numpy reference
(``np.add.at`` over ``abs(key) % num_clusters``).

    python chip_smoke.py            # one chip: vmap backend, 8 slots,
                                    # jnp reduce path and Pallas kernel path
    python chip_smoke.py --chips 4  # four chips: shard_map on a 4-device
                                    # mesh vs vmap on device 0, 4 slots

Earlier lines report wall times, overflow, reuse decisions, byte counts,
compile-cache hits and peak device memory. The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every batch
matched. With no TPU, or on any failure, the script exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.mapreduce import MapReduceConfig, MapReduceJob  # noqa: E402
from repro.core.schedule_cache import ReusePolicy  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.serve import steady_state_loop  # noqa: E402

MiB = 1 << 20


def make_vocab(seed: int, num_keys: int) -> np.ndarray:
    """The job's distinct words: ``num_keys`` non-negative int32 key hashes."""
    return np.random.default_rng(seed).integers(
        0, np.iinfo(np.int32).max, size=num_keys, dtype=np.int32)


def make_batch(seed: int, batch: int, vocab: np.ndarray, num_slots: int,
               pairs_per_slot: int, alpha: float, value_dim: int):
    """One batch of ``(keys, values, valid)`` host arrays, slot-major.

    Keys draw Zipf(``alpha``) ranks over ``vocab`` by inverse CDF; values
    are small integers in 0..3, so every f32 sum stays exact below 2^24.
    """
    rng = np.random.default_rng([seed, batch])
    cdf = np.cumsum(np.arange(1, vocab.size + 1, dtype=np.float64) ** -alpha)
    ranks = np.searchsorted(cdf / cdf[-1], rng.random(num_slots * pairs_per_slot))
    keys = vocab[np.minimum(ranks, vocab.size - 1)].reshape(num_slots, pairs_per_slot)
    values = rng.integers(0, 4, size=(num_slots, pairs_per_slot, value_dim),
                          dtype=np.int8).astype(np.float32)
    valid = np.ones((num_slots, pairs_per_slot), bool)
    return keys, values, valid


def reference(keys, values, valid, num_clusters: int):
    """Word-count oracle: per-cluster value sums and pair counts, in numpy."""
    cl = (np.abs(keys.astype(np.int64)) % num_clusters).reshape(-1)
    ok = valid.reshape(-1)
    out = np.zeros((num_clusters, values.shape[-1]), np.float64)
    np.add.at(out, cl[ok], values.reshape(-1, values.shape[-1])[ok])
    counts = np.zeros(num_clusters, np.float64)
    np.add.at(counts, cl[ok], 1.0)
    return out.astype(np.float32), counts.astype(np.float32)


def bit_equal(a, b) -> bool:
    """Same shape and the same f32 bit patterns."""
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32), b.view(np.uint32))


class CacheCounter:
    """Counts JAX's persistent compilation-cache events as they happen."""

    EVENTS = ("/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self):
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw):
        if event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> dict:
        return {"hits": self.counts["cache_hits"],
                "misses": self.counts["cache_misses"]}


def run_wordcount(batches, refs, *, num_slots: int, num_clusters: int,
                  use_kernels: bool, backend: str = "vmap", mesh=None,
                  pipeline_chunks: int = 4, log=print, cache_counter=None):
    """Serve ``batches`` through one reused-plan job and check each one.

    ``batches`` are device-ready ``(keys, values, valid)`` tuples with a
    leading ``(num_slots,)`` axis; ``refs`` the matching ``(values,
    counts)`` numpy references. Returns the per-batch ``JobResult`` list;
    raises ``AssertionError`` on the first batch that is not bit-identical.
    """
    job = MapReduceJob(
        lambda shard: shard,
        MapReduceConfig(num_slots=num_slots, num_clusters=num_clusters,
                        scheduler="os4m", pipeline_chunks=pipeline_chunks,
                        use_kernels=use_kernels, reuse=ReusePolicy()),
        backend=backend, mesh=mesh,
    )
    results, cache_at = [], []

    def on_batch(i, res, wall):
        ref_values, ref_counts = refs[i]
        values_ok = bit_equal(res.values, ref_values)
        counts_ok = bit_equal(res.counts, ref_counts)
        results.append(res)
        cache_at.append(cache_counter.snapshot() if cache_counter else None)
        log(f"  batch {i} ({'cold, compile included' if i == 0 else 'warm'}): "
            f"wall {wall:.6f} s, "
            f"{'reuse' if res.reused else 'plan'} ({res.plan_reason}), "
            f"overflow {res.overflow}, shuffle {res.shuffle_bytes} B in "
            f"{res.shuffle_rows} rows ({res.shuffle_pairs} non-local pairs), "
            f"makespan/ideal {res.schedule.finish_ratio:.6f}, "
            f"values {'bit-identical' if values_ok else 'MISMATCH'}, "
            f"counts {'bit-identical' if counts_ok else 'MISMATCH'}")
        if not (values_ok and counts_ok):
            raise AssertionError(f"batch {i} differs from the numpy reference")
        if res.overflow:
            raise AssertionError(f"batch {i} dropped {res.overflow} pairs")

    start = cache_counter.snapshot() if cache_counter else None
    tele = steady_state_loop(job, batches, on_batch=on_batch)
    if cache_counter is not None and len(cache_at) > 1:
        cold = {k: cache_at[0][k] - start[k] for k in start}
        warm = {k: cache_at[-1][k] - cache_at[0][k] for k in start}
        log(f"  compile cache: cold batch {cold['hits']} hits / "
            f"{cold['misses']} misses; warm batches {warm['hits']} hits / "
            f"{warm['misses']} misses (executables traced in total: "
            f"{tele['jit_misses']})")
    return results, tele


def _path_name(use_kernels: bool) -> str:
    from repro import kernels

    if not use_kernels:
        return "jnp"
    return f"Pallas kernels ({'interpreted' if kernels.interpret() else 'compiled'})"


def compare_mesh_to_one_device(host, refs, *, num_clusters: int, log=print,
                               cache_counter=None):
    """The four-chip phase: shard_map over a 4-device mesh vs vmap on one.

    ``host`` holds numpy batches with a leading ``(4,)`` slot axis. Each
    reduce path runs as a shard_map job (one Reduce slot per device, the
    copy phase a real all-to-all) and as a vmap job on the default
    device; both are checked against ``refs`` and against each other bit
    for bit. Raises ``AssertionError`` on any difference, or when the
    batches do not span four devices.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("slots",))
    sharded = [tuple(jax.device_put(a, NamedSharding(mesh, P("slots")))
                     for a in h) for h in host]
    spans = sorted({d.id for a in sharded[0] for d in a.sharding.device_set})
    log(f"mesh: devices {[d.id for d in mesh.devices.flat]}; batch shards on "
        f"devices {spans}")
    if len(spans) != 4:
        raise AssertionError(f"the shard_map batch spans devices {spans}, not 4")
    local = [tuple(jnp.asarray(a) for a in h) for h in host]
    for use_kernels in (False, True):
        log(f"reduce path: {_path_name(use_kernels)}; shard_map on the 4-device mesh")
        sm, _ = run_wordcount(sharded, refs, num_slots=4, num_clusters=num_clusters,
                              use_kernels=use_kernels, backend="shard_map",
                              mesh=mesh, log=log, cache_counter=cache_counter)
        log(f"reduce path: {_path_name(use_kernels)}; vmap on device 0")
        vm, _ = run_wordcount(local, refs, num_slots=4, num_clusters=num_clusters,
                              use_kernels=use_kernels, log=log,
                              cache_counter=cache_counter)
        for i, (a, b) in enumerate(zip(sm, vm)):
            if not (bit_equal(a.values, b.values) and bit_equal(a.counts, b.counts)):
                raise AssertionError(f"batch {i}: shard_map differs from vmap")
        log(f"  shard_map == vmap bit for bit on all {len(sm)} batches")


def _device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batches", type=int, default=3)
    args = ap.parse_args(argv)

    device = _device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()  # before the first compile

    num_slots = 8 if args.chips == 1 else 4
    pairs_per_slot, num_clusters, value_dim = 1 << 20, 65536, 8
    num_keys, alpha = 10 ** 6, 1.1
    pairs = num_slots * pairs_per_slot
    print(f"device: {device}; compile cache: {cache_dir}")
    print(f"job: {pairs} pairs per batch ({num_slots} slots x "
          f"{pairs_per_slot}), {num_keys} Zipf({alpha}) keys -> "
          f"{num_clusters} clusters, values ({value_dim},) f32, "
          f"{args.batches} batches")
    in_bytes = pairs * (value_dim * 4 + 4 + 1)
    print(f"memory reckoned: values {pairs * value_dim * 4 / MiB:.0f} MiB, keys "
          f"{pairs * 4 / MiB:.0f} MiB, valid {pairs / MiB:.0f} MiB per batch; "
          f"send slabs about {in_bytes / MiB:.0f} MiB again; expected peak "
          f"about 1-2 GiB of {(jax.devices()[0].memory_stats() or {}).get('bytes_limit', 0) / 2**30:.1f} GiB")

    from repro.kernels.wave_timer import ops as wt_ops

    print(f"wave timer: tick backend {wt_ops.backend()!r}; these jobs measure "
          f"no wave timings (estimate_speeds off), so no executor falls back "
          f"to host-fenced waves")

    t0 = time.perf_counter()
    vocab = make_vocab(args.seed, num_keys)
    host = [make_batch(args.seed, b, vocab, num_slots, pairs_per_slot, alpha,
                       value_dim) for b in range(args.batches)]
    t1 = time.perf_counter()
    refs = [reference(*h, num_clusters) for h in host]
    t2 = time.perf_counter()
    print(f"set-up: data {t1 - t0:.6f} s, numpy reference {t2 - t1:.6f} s")

    counter = CacheCounter()
    if args.chips == 1:
        batches = [tuple(jnp.asarray(a) for a in h) for h in host]
        for use_kernels in (False, True):
            print(f"reduce path: {_path_name(use_kernels)}")
            run_wordcount(batches, refs, num_slots=num_slots,
                          num_clusters=num_clusters, use_kernels=use_kernels,
                          cache_counter=counter)
    else:
        compare_mesh_to_one_device(host, refs, num_clusters=num_clusters,
                                   cache_counter=counter)
    print(f"peak_bytes_in_use per device: {_peak_bytes(jax.devices()[:args.chips])}")

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
