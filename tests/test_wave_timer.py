"""The ``kernels/wave_timer`` subsystem (ISSUE 5 tentpole).

* interpret-mode tick kernel: monotone stamps, word-format round trip;
* calibration: ticks↔seconds round trip, host-bracketed ``calibrate``;
* ``ops.read_ticks`` inside jitted / shard_map programs (per-shard
  stamps, ordering by data dependency);
* CPU fallback identity: with no tick source the measured executor
  degrades to the host-fenced path built on ``shard_ready_seconds``;
* bit-identity: overlapped-measured outputs == unmeasured == sequential
  (vmap) reference — stamps and barriers are value identities.

Mesh tests skip below 8 host devices (CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import mesh_timing as mt
from repro.core.mapreduce import MapReduceConfig, MapReduceJob
from repro.kernels.wave_timer import calibration as cal
from repro.kernels.wave_timer import ops as wt_ops
from repro.kernels.wave_timer import ref as wt_ref
from repro.kernels.wave_timer import wave_timer as wt


def _mesh(m):
    from jax.sharding import Mesh

    if len(jax.devices()) < m:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return Mesh(np.asarray(jax.devices()[:m]), ("mr_slots",))


def _batch(seed, m, K=512, key_mod=503):
    rng = np.random.default_rng(seed)
    keys = (rng.zipf(1.25, size=(m, K)) % key_mod).astype(np.int32)
    return (jnp.asarray(keys), jnp.asarray(np.ones((m, K, 4), np.float32)),
            jnp.asarray(np.ones((m, K), bool)))


# ---------------------------------------------------------------------------
# Word format (ref oracle).
# ---------------------------------------------------------------------------


class TestTickWords:
    def test_split_combine_round_trip(self):
        vals = np.asarray([0, 1, 2**31, 2**32 - 1, 2**32, 2**40 + 12345,
                           time.perf_counter_ns()], np.int64)
        words = wt_ref.split_ticks(vals)
        assert words.shape == vals.shape + (2,)
        assert words.dtype == np.uint32
        back = wt_ref.combine_ticks(words)
        assert np.array_equal(back, vals)

    def test_combine_validates_trailing_axis(self):
        with pytest.raises(ValueError):
            wt_ref.combine_ticks(np.zeros((3, 4), np.uint32))

    def test_read_ticks_ref_is_monotone(self):
        a = wt_ref.combine_ticks(wt_ref.read_ticks_ref())
        b = wt_ref.combine_ticks(wt_ref.read_ticks_ref())
        assert b >= a > 0


# ---------------------------------------------------------------------------
# The interpret-mode Pallas kernel.
# ---------------------------------------------------------------------------


class TestInterpretKernel:
    def test_interpret_ticks_monotone(self):
        """Two sequential kernel reads advance (the perf_counter body)."""
        t1 = wt_ref.combine_ticks(np.asarray(jax.device_get(
            wt.read_ticks_pallas(jnp.float32(1.0), interpret=True))))
        time.sleep(1e-3)
        t2 = wt_ref.combine_ticks(np.asarray(jax.device_get(
            wt.read_ticks_pallas(jnp.float32(2.0), interpret=True))))
        assert int(t2) > int(t1) > 0

    def test_interpret_tick_interval_tracks_wall_clock(self):
        t1 = int(wt_ref.combine_ticks(np.asarray(jax.device_get(
            wt.read_ticks_pallas(jnp.float32(1.0), interpret=True)))))
        time.sleep(0.05)
        t2 = int(wt_ref.combine_ticks(np.asarray(jax.device_get(
            wt.read_ticks_pallas(jnp.float32(2.0), interpret=True)))))
        # host-ns ticks: 50 ms sleep is >= 4e7 ticks (loose lower bound)
        assert t2 - t1 >= 4e7

    def test_compiled_mode_requires_device_counter(self):
        if wt.device_tick_primitive() is not None:
            pytest.skip("toolchain exposes a device counter")
        with pytest.raises(RuntimeError):
            wt.read_ticks_pallas(jnp.float32(0.0), interpret=False)


# ---------------------------------------------------------------------------
# Calibration.
# ---------------------------------------------------------------------------


class TestCalibration:
    def test_round_trip(self):
        c = cal.TickCalibration(2.5e-9, source="test")
        secs = np.asarray([0.0, 1e-6, 3.2e-3, 1.5])
        back = c.ticks_to_seconds(c.seconds_to_ticks(secs))
        assert np.allclose(back, secs, rtol=0, atol=3e-9)

    def test_host_ns_unit_is_exact(self):
        assert cal.HOST_NS.seconds_per_tick == 1e-9
        assert cal.HOST_NS.ticks_to_seconds(1_000_000_000) == pytest.approx(1.0)

    def test_validates_scale(self):
        for bad in (0.0, -1e-9, float("nan"), float("inf")):
            with pytest.raises(ValueError):
                cal.TickCalibration(bad)

    def test_calibrate_host_counter_recovers_ns_scale(self):
        """Bracketing the host ns counter must land near 1e-9 s/tick.
        Very loose bounds: the container's scheduler can stretch any one
        sleep, but the median over repeats stays the right magnitude."""
        c = cal.calibrate(
            lambda: int(wt_ref.combine_ticks(wt_ref.read_ticks_ref())),
            sleep_seconds=0.02, repeats=3)
        assert 2e-10 < c.seconds_per_tick < 5e-9

    def test_calibrate_rejects_frozen_counter(self):
        with pytest.raises(RuntimeError):
            cal.calibrate(lambda: 42, sleep_seconds=0.0, repeats=2)

    def test_ops_tick_calibration_on_cpu_is_host_ns(self):
        assert wt_ops.backend() == "callback"    # this container is CPU
        assert wt_ops.tick_calibration() is cal.HOST_NS


# ---------------------------------------------------------------------------
# The jit-safe op.
# ---------------------------------------------------------------------------


class TestReadTicksOp:
    def test_backend_resolution_and_force(self):
        assert wt_ops.available()
        with wt_ops.force_backend("none"):
            assert wt_ops.backend() == "none"
            assert not wt_ops.available()
            with pytest.raises(RuntimeError):
                wt_ops.read_ticks(jnp.float32(0.0))
        assert wt_ops.available()                # restored on exit
        with pytest.raises(ValueError):
            wt_ops.force_backend("warp-core")

    def test_stamp_through_is_value_identity(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal((64, 8)),
                        jnp.float32)
        y, _ = jax.jit(lambda a: wt_ops.stamp_through(a))(x)
        assert np.array_equal(np.asarray(x), np.asarray(y))
        ids = jnp.arange(-5, 11, dtype=jnp.int32)
        out, _ = jax.jit(lambda a: wt_ops.stamp_through(a, jnp.float32(3)))(ids)
        assert np.array_equal(np.asarray(ids), np.asarray(out))

    def test_stamp_through_brackets_compute(self):
        """Two pass-through stamps around a computation must bracket it:
        the second consumes the compute's output, the first produces the
        compute's input — true buffer deps the scheduler cannot undo
        (anchoring alone, or optimization_barrier, demonstrably can)."""

        @jax.jit
        def prog(x):
            x, t0 = wt_ops.stamp_through(x)
            y = jnp.tanh(x @ x.T)
            for _ in range(4):
                y = jnp.tanh(y @ y)
            y, t1 = wt_ops.stamp_through(y)
            return y, t0, t1

        for _ in range(3):                   # steady state, not just trace run
            _, w0, w1 = prog(jnp.ones((128, 128)))
        a = int(wt_ref.combine_ticks(np.asarray(jax.device_get(w0))))
        b = int(wt_ref.combine_ticks(np.asarray(jax.device_get(w1))))
        assert b >= a > 0

    def test_per_shard_stamps_under_shard_map(self):
        from jax.sharding import PartitionSpec as P

        from repro import compat

        m = 8
        mesh = _mesh(m)

        def body(x):
            x, t0 = wt_ops.stamp_through(x)
            y = jnp.tanh(x @ x.T)
            y, t1 = wt_ops.stamp_through(y)
            return y, jnp.stack([t0, t1])[None]

        fn = jax.jit(compat.shard_map(
            body, mesh=mesh, in_specs=(P("mr_slots", None),),
            out_specs=(P("mr_slots", None), P("mr_slots", None))))
        for _ in range(2):
            _, words = fn(jnp.ones((m * 32, 32)))
        ticks = wt_ref.combine_ticks(
            np.asarray(jax.device_get(words)).reshape(m, 2, 2))
        assert (ticks[:, 1] >= ticks[:, 0]).all()   # per-shard monotone
        assert (ticks > 0).all()


# ---------------------------------------------------------------------------
# Executor integration: fallback identity + bit-identity.
# ---------------------------------------------------------------------------


class TestMeasuredExecutorIntegration:
    m = 8

    def _jobs(self, mesh, **kw):
        meas = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipeline_chunks=3, estimate_speeds=True, **kw),
            backend="shard_map", mesh=mesh)
        return meas

    def test_cpu_fallback_uses_shard_ready_seconds(self, monkeypatch):
        """With no tick source the measured executor must degrade to the
        fenced path built on shard_ready_seconds (the documented
        fallback), with bit-identical outputs."""
        mesh = _mesh(self.m)
        calls = []
        real = mt.shard_ready_seconds

        def spy(outputs, num_slots, t0):
            calls.append(num_slots)
            return real(outputs, num_slots, t0)

        monkeypatch.setattr(mt, "shard_ready_seconds", spy)
        ref = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipeline_chunks=3), backend="vmap")
        with wt_ops.force_backend("none"), \
                pytest.warns(RuntimeWarning, match="host-fenced executor"):
            job = self._jobs(mesh)
            b = _batch(0, self.m)
            r, v = job.run(b), ref.run(b)
        assert calls, "fenced fallback never consulted shard_ready_seconds"
        assert job.last_wave_timings is not None
        assert job.last_wave_timings.seconds.shape[0] == self.m
        assert np.array_equal(np.asarray(r.values), np.asarray(v.values))
        assert np.array_equal(np.asarray(r.counts), np.asarray(v.counts))

    def test_tick_path_does_not_touch_host_fences(self, monkeypatch):
        mesh = _mesh(self.m)

        def boom(*a, **k):                       # pragma: no cover - guard
            raise AssertionError("tick path must not host-fence")

        monkeypatch.setattr(mt, "shard_ready_seconds", boom)
        job = self._jobs(mesh)
        job.run(_batch(0, self.m))
        assert job.last_wave_timings is not None
        assert job.last_wave_timings.valid

    def test_overlapped_measured_bit_identical_to_sequential(self):
        """ISSUE 5 acceptance: overlapped-measured outputs are bit-equal
        to the Hadoop-style sequential phase B AND the unmeasured
        overlapped path on the same batches."""
        mesh = _mesh(self.m)
        measured = self._jobs(mesh)
        unmeasured = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipeline_chunks=3), backend="shard_map", mesh=mesh)
        sequential = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipelined=False), backend="vmap")
        for i in range(2):
            b = _batch(i, self.m)
            r_m, r_u, r_s = measured.run(b), unmeasured.run(b), sequential.run(b)
            assert measured.last_wave_timings is not None
            for other in (r_u, r_s):
                assert np.array_equal(np.asarray(r_m.values),
                                      np.asarray(other.values))
                assert np.array_equal(np.asarray(r_m.counts),
                                      np.asarray(other.counts))

    def test_ticks_buffer_shape_matches_plan_waves(self):
        mesh = _mesh(self.m)
        job = self._jobs(mesh)
        job.run(_batch(0, self.m))
        t = job.last_wave_timings
        assert t.seconds.shape[0] == self.m
        assert t.seconds.shape[1] >= 1
        assert (t.seconds >= 0).all()
