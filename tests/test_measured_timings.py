"""The measured-mesh feedback subsystem (ISSUE 4 tentpole, ISSUE 5 rework).

* shard_map phase B runs the SAME overlapped pipeline as unmeasured mode
  with **on-device wave tick stamps** (``kernels/wave_timer``) feeding
  the estimator (synthetic model retired); outputs stay bit-identical to
  the vmap reference; the host-fenced executor survives as the explicit
  no-tick-source fallback;
* an injected slowdown on the measured path triggers a ``speed_drift``
  replan; measured speeds ride ``CachedSchedule.to_json`` round trips;
* slowdown factors are **wall-clock multipliers** (2.0 ⇒ twice as slow)
  on both the measured and the synthetic path (ISSUE 5 bugfix);
* ``shard_ready_seconds`` attributes completion in completion order — an
  out-of-order straggler no longer poisons later slots (ISSUE 5 bugfix);
* zero-second / degenerate observations never reach the estimator
  (ISSUE 5 bugfix);
* a wave with an idle slot (no clusters assigned) survives;
* the schedule-cache drift check is device-resident on shard_map (the
  baseline ``K^(i)`` is uploaded once, sharded, and reused);
* :mod:`repro.core.mesh_timing` unit behaviour (no mesh needed).

Mesh tests follow the repo convention: skip below 8 host devices (CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Timing *magnitudes* on the CI container are contention noise (8 virtual
devices over ~2 cores), so assertions about measured speeds use strong
injected factors and generous margins; reuse-mechanics tests disable the
speed-drift trigger outright (``max_speed_drift=1e9``) so honest
measurement noise cannot flake them.
"""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import mesh_timing as mt
from repro.core.mapreduce import MapReduceConfig, MapReduceJob
from repro.core.schedule_cache import CachedSchedule, ReusePolicy, drift_metric
from repro.kernels.wave_timer import ops as wt_ops


def _mesh(m):
    from jax.sharding import Mesh

    if len(jax.devices()) < m:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return Mesh(np.asarray(jax.devices()[:m]), ("mr_slots",))


def _batch(seed, m, K=512, key_mod=503, alpha=1.25):
    rng = np.random.default_rng(seed)
    keys = (rng.zipf(alpha, size=(m, K)) % key_mod).astype(np.int32)
    vals = np.ones((m, K, 4), np.float32)
    valid = np.ones((m, K), bool)
    return (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))


def _measured_job(m, mesh, n=24, **cfg_kw):
    cfg_kw.setdefault("reuse", ReusePolicy(max_drift=0.3, max_speed_drift=0.25))
    return MapReduceJob(
        lambda s: s,
        MapReduceConfig(num_slots=m, num_clusters=n, scheduler="bss",
                        pipeline_chunks=3, estimate_speeds=True, **cfg_kw),
        backend="shard_map", mesh=mesh)


# ---------------------------------------------------------------------------
# Config resolution / validation (no mesh needed).
# ---------------------------------------------------------------------------


def test_measure_timings_requires_shard_map():
    with pytest.raises(ValueError):
        MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=2, num_clusters=8, estimate_speeds=True,
            measure_timings=True), backend="vmap")


def test_measure_timings_requires_estimator():
    mesh = _mesh(1) if len(jax.devices()) >= 1 else None
    with pytest.raises(ValueError):
        MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=1, num_clusters=8, measure_timings=True),
            backend="shard_map", mesh=mesh)


def test_vmap_job_stays_on_synthetic_model():
    job = MapReduceJob(lambda s: s, MapReduceConfig(
        num_slots=4, num_clusters=16, estimate_speeds=True), backend="vmap")
    assert not job._measure_timings
    job.run(_batch(0, 4, K=256, key_mod=97))
    assert job.last_wave_timings is None        # synthetic path
    assert job.speed_estimator.observations == 1


# ---------------------------------------------------------------------------
# WaveTimings / shard_ready_seconds units.
# ---------------------------------------------------------------------------


class TestWaveTimings:
    def test_accumulates_and_sums(self):
        t = mt.WaveTimings.empty(3, 2)
        t.record(0, [0.1, 0.2, 0.3])
        t.record(1, [0.4, 0.1, 0.0])
        assert np.allclose(t.slot_seconds(), [0.5, 0.3, 0.3])

    def test_observation_applies_injected_slowdown(self):
        """ISSUE 5 bugfix pin: the slowdown factor is a wall-clock
        MULTIPLIER — a 2x factor yields 2x the measured seconds (the old
        code divided, so "slowdown 2" made the slot look faster)."""
        t = mt.WaveTimings.empty(2, 1)
        t.record(0, [1.0, 1.0])
        t.slot_work = np.asarray([10.0, 10.0])
        work, secs = t.observation(np.asarray([1.0, 2.0]))
        # the 2x-slow slot reports DOUBLE the measured wall-clock
        assert np.allclose(secs, [1.0, 2.0])
        assert np.allclose(work, [10.0, 10.0])

    def test_from_ticks_round_trip(self):
        """(slots, waves, 2) start/end stamps become per-wave seconds."""
        base = 1_000_000
        ticks = np.asarray([
            [[base, base + 100], [base + 200, base + 500]],
            [[base, base + 400], [base + 400, base + 400]],
        ], np.int64)
        t = mt.WaveTimings.from_ticks(ticks, 1e-9)
        assert t.valid
        assert np.allclose(t.seconds, [[100e-9, 300e-9], [400e-9, 0.0]])
        assert np.allclose(t.slot_seconds(), [400e-9, 400e-9])

    def test_from_ticks_wrapped_stamp_is_invalid_not_negative(self):
        ticks = np.asarray([[[100, 40]]], np.int64)   # end < start: wrap/fault
        t = mt.WaveTimings.from_ticks(ticks, 1e-9)
        assert not t.valid
        assert (t.seconds >= 0).all()

    def test_from_ticks_validates_shape(self):
        with pytest.raises(ValueError):
            mt.WaveTimings.from_ticks(np.zeros((4, 2)), 1e-9)

    def test_shard_ready_seconds_fallback_single_device(self):
        arr = jnp.ones((8, 4))       # one addressable shard < num_slots
        secs = mt.shard_ready_seconds([arr], 4, time.perf_counter())
        assert secs.shape == (4,)
        assert (secs >= 0).all()


class _FakeBuf:
    """A device buffer that becomes ready at a wall-clock deadline.

    ``pollable=False`` drops the ``is_ready`` attribute entirely, standing
    in for runtimes whose buffers cannot report readiness.
    """

    def __init__(self, ready_at: float, pollable: bool = True):
        self._ready_at = ready_at
        if pollable:
            self.is_ready = lambda: time.perf_counter() >= self._ready_at

    def block_until_ready(self):
        delay = self._ready_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        return self


class _FakeShard:
    def __init__(self, row_start: int, data: _FakeBuf):
        self.index = (slice(row_start, row_start + 2),)
        self.data = data


class _FakeArray:
    """Duck-typed sharded array: 4 slots x 2 rows, per-slot readiness."""

    def __init__(self, ready_at, pollable: bool = True):
        self.shape = (8, 4)
        self.addressable_shards = [
            _FakeShard(2 * s, _FakeBuf(t, pollable))
            for s, t in enumerate(ready_at)
        ]


class TestCompletionOrderAwait:
    def test_fast_shard_does_not_inherit_straggler_timestamp(self):
        """ISSUE 5 regression: slots are stamped in COMPLETION order. With
        an injected straggler at slot 0 and instantly-ready slots 1..3,
        the old serial slot-id-order await charged every later slot the
        straggler's ~80 ms; completion-order polling stamps them early."""
        t0 = time.perf_counter()
        straggle = 0.08
        arr = _FakeArray([t0 + straggle, t0, t0, t0])
        secs = mt.shard_ready_seconds([arr], 4, t0)
        assert secs[0] >= straggle * 0.9          # the straggler earns its bound
        for fast in (1, 2, 3):
            assert secs[fast] < straggle * 0.5, (
                f"slot {fast} inherited the straggler's timestamp: {secs}")

    def test_out_of_order_completion_attributed_per_slot(self):
        """Completion times in reverse slot order come back per-slot."""
        t0 = time.perf_counter()
        deadlines = [t0 + 0.06, t0 + 0.04, t0 + 0.02, t0]
        secs = mt.shard_ready_seconds([_FakeArray(deadlines)], 4, t0)
        assert np.all(np.diff(secs) < 0)          # slot 3 first, slot 0 last
        assert secs[0] >= 0.05

    def test_unpollable_buffers_use_serial_await(self):
        """Buffers without is_ready degrade to the serial slot-order await
        (documented upper-bound attribution) instead of crashing."""
        t0 = time.perf_counter()
        arr = _FakeArray([t0 + 0.01] * 4, pollable=False)
        secs = mt.shard_ready_seconds([arr], 4, t0)
        assert (secs >= 0.009).all()


# ---------------------------------------------------------------------------
# The measured loop on a mesh.
# ---------------------------------------------------------------------------


class TestMeasuredMesh:
    m = 8

    def test_measured_timings_drive_estimator_and_replan(self):
        """Measured per-device tick clocks (not synthetic) update the
        estimator; an injected slowdown trips a speed_drift replan;
        outputs stay bit-identical to the unperturbed vmap reference
        throughout — all WITHOUT wave fencing (the overlapped program)."""
        mesh = _mesh(self.m)
        # Key drift must not mask the straggler trigger: with a tight
        # max_drift a zipf batch can trip a "drift" replan at the same
        # batch as the injected slowdown, absorbing the speed change into
        # the new plan before the speed check ever fires.
        job = _measured_job(self.m, mesh,
                            reuse=ReusePolicy(max_drift=0.8,
                                              max_speed_drift=0.25))
        ref = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipeline_chunks=3), backend="vmap")
        assert job._measure_timings
        reasons = []
        for i in range(7):
            if i == 3:
                job.set_slot_slowdown(1, 3.0)    # slot 1 now 3x slower
            r = job.run(_batch(i, self.m))
            v = ref.run(_batch(i, self.m))
            assert np.array_equal(np.asarray(r.values), np.asarray(v.values))
            assert np.array_equal(np.asarray(r.counts), np.asarray(v.counts))
            reasons.append(r.plan_reason)
        # the first contact flipped the job to external/measured mode:
        # the synthetic model can never dilute the estimate again
        assert job._external_timings
        assert job.last_wave_timings is not None
        assert job.last_wave_timings.seconds.shape[0] == self.m
        # measured batches accumulated observations
        assert job.speed_estimator.observations >= 2
        # injected straggler detected from measured seconds -> replan
        assert job.schedule_cache.speed_replans >= 1
        assert "speed_drift" in reasons
        sp = job.speed_estimator.speeds()
        assert sp[1] < 0.85                      # slot 1 visibly slow
        assert sp[1] == sp.min()

    def test_tick_path_first_batch_is_already_valid(self):
        """On-device tick stamps execute with the program, AFTER
        compilation — so (unlike the fenced fallback) even the first,
        freshly traced batch is a valid speed sample."""
        mesh = _mesh(self.m)
        job = _measured_job(self.m, mesh)
        assert wt_ops.available()                # this container: CPU callback
        job.run(_batch(0, self.m))
        assert job.last_wave_timings is not None
        assert job.last_wave_timings.valid
        assert job.speed_estimator.observations == 1

    def test_fenced_fallback_skips_compiled_waves(self):
        """With the tick source forced off, the measured executor falls
        back to host-fenced timing, which must keep skipping batches
        whose timed waves traced/compiled (compilation is not a speed
        signal)."""
        mesh = _mesh(self.m)
        with wt_ops.force_backend("none"), \
                pytest.warns(RuntimeWarning, match="host-fenced executor"):
            job = _measured_job(self.m, mesh)
            job.run(_batch(0, self.m))
            # batch 0 traced/compiled its wave programs -> measured, invalid
            assert job.last_wave_timings is not None
            assert not job.last_wave_timings.valid
            assert job.speed_estimator.observations == 0
            job.run(_batch(1, self.m))
            assert job.last_wave_timings.valid
            assert job.speed_estimator.observations == 1

    def test_idle_slot_wave_survives(self):
        """A schedule that leaves one slot without clusters still executes,
        measures, and reduces correctly (capacity-shaped waves pad)."""
        mesh = _mesh(self.m)
        # fewer clusters than slots => some slots hold no cluster
        job = _measured_job(self.m, mesh, n=5,
                            reuse=ReusePolicy(max_drift=0.5))
        ref = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=5, scheduler="bss",
            pipeline_chunks=3), backend="vmap")
        for i in range(3):
            b = _batch(i, self.m, key_mod=5)
            r, v = job.run(b), ref.run(b)
            idle = np.setdiff1d(np.arange(self.m),
                                np.unique(r.schedule.assignment))
            assert idle.size > 0                 # the fixture is real
            assert np.array_equal(np.asarray(r.values), np.asarray(v.values))
            assert np.array_equal(np.asarray(r.counts), np.asarray(v.counts))
        sp = job.speed_estimator.speeds(default_ones=True)
        assert np.isfinite(sp).all()

    def test_measured_speeds_roundtrip_through_snapshot_json(self):
        """Measured speeds land in the replanned snapshot and survive
        CachedSchedule.to_json round trips."""
        mesh = _mesh(self.m)
        job = _measured_job(self.m, mesh)
        job.set_slot_slowdown(2, 3.0)
        for i in range(6):
            r = job.run(_batch(i, self.m))
            if r.plan_reason == "speed_drift":
                break
        snap = job.schedule_cache.snapshot
        assert not np.allclose(snap.slot_speeds, 1.0)   # measured, non-nominal
        clone = CachedSchedule.from_json(json.loads(json.dumps(snap.to_json())))
        assert np.allclose(clone.slot_speeds, snap.slot_speeds)
        assert np.array_equal(clone.schedule.assignment,
                              snap.schedule.assignment)

    def test_sequential_phase_b_measured_single_wave(self):
        mesh = _mesh(self.m)
        job = _measured_job(self.m, mesh, pipelined=False,
                            reuse=ReusePolicy(max_drift=0.5))
        ref = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=self.m, num_clusters=24, scheduler="bss",
            pipelined=False), backend="vmap")
        for i in range(2):
            b = _batch(i, self.m)
            r, v = job.run(b), ref.run(b)
            assert np.array_equal(np.asarray(r.values), np.asarray(v.values))
        assert job.last_wave_timings.seconds.shape == (self.m, 1)


# ---------------------------------------------------------------------------
# Device-resident drift check.
# ---------------------------------------------------------------------------


class TestDeviceResidentDrift:
    m = 8

    # Reuse-mechanics tests: the speed-drift trigger is disabled (huge
    # threshold) so honest measurement noise on the shared-core CI mesh
    # cannot replan mid-test and swap the snapshot under the assertions.
    policy = ReusePolicy(max_drift=0.3, max_speed_drift=1e9)

    def test_baseline_uploaded_once_and_reused(self):
        mesh = _mesh(self.m)
        job = _measured_job(self.m, mesh, reuse=self.policy)
        assert job.schedule_cache.drift_fn is not None
        job.run(_batch(0, self.m))
        snap = job.schedule_cache.snapshot
        assert snap._hist_dev is None            # nothing checked yet
        job.run(_batch(1, self.m))
        dev = snap._hist_dev
        assert dev is not None                   # uploaded by the check...
        job.run(_batch(2, self.m))
        assert snap._hist_dev is dev             # ...and NOT re-uploaded
        # the resident baseline is sharded over the mesh, one row per device
        assert len(dev.addressable_shards) == self.m

    def test_sharded_drift_matches_host_metric(self):
        mesh = _mesh(self.m)
        job = _measured_job(self.m, mesh, reuse=self.policy)
        job.run(_batch(0, self.m))
        r = job.run(_batch(1, self.m))
        snap = job.schedule_cache.snapshot
        fresh = np.asarray([np.bincount(
            np.abs(np.asarray(_batch(1, self.m)[0][i])) % 24, minlength=24)
            for i in range(self.m)], np.float32)
        want = float(drift_metric(snap.local_hist.astype(np.float32),
                                  fresh, "l1"))
        assert r.drift == pytest.approx(want, abs=1e-5)

    def test_vmap_jobs_have_no_drift_fn(self):
        job = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=4, num_clusters=16, reuse=ReusePolicy()), backend="vmap")
        assert job.schedule_cache.drift_fn is None


# ---------------------------------------------------------------------------
# ISSUE 5 bugfix pins: zero-second guard + slowdown factor direction.
# ---------------------------------------------------------------------------


class TestZeroSecondGuard:
    def test_estimator_skips_zero_and_nonfinite_seconds(self):
        from repro.core.slot_speeds import SlotSpeedEstimator

        est = SlotSpeedEstimator(4)
        est.update(np.ones(4), np.zeros(4))            # all-zero seconds
        assert est.observations == 0
        assert est.speeds() is None                    # still "no data"
        est.update(np.ones(4), [np.inf, np.nan, -1.0, 0.0])
        assert est.observations == 0
        # a mixed batch only folds in the usable slot
        est.update(np.ones(4), [0.0, 0.5, 0.0, np.inf])
        assert est.observations == 1
        sp = est.speeds()
        assert np.isfinite(sp).all() and (sp > 0).all()

    def test_empty_wave_timings_never_reach_estimator(self):
        """WaveTimings.empty(m, 0) (and any all-zero batch) must not flip
        the job to external-measurement mode or count as an observation —
        the old code fed seconds == 0 straight to the estimator."""
        job = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=4, num_clusters=16, estimate_speeds=True),
            backend="vmap")
        planned = _fake_plan(job)
        job._observe_measured(mt.WaveTimings.empty(4, 0), planned)
        assert not job._external_timings
        assert job.speed_estimator.observations == 0
        assert job.speed_estimator.speeds() is None

    def test_all_invalid_batch_is_skipped(self):
        job = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=4, num_clusters=16, estimate_speeds=True),
            backend="vmap")
        planned = _fake_plan(job)
        t = mt.WaveTimings.empty(4, 2)
        t.record(0, [0.1, 0.2, 0.3, 0.4])
        t.valid = False                                # compile-polluted
        job._observe_measured(t, planned)
        assert not job._external_timings
        assert job.speed_estimator.observations == 0


def _fake_plan(job):
    """A minimal CachedSchedule for observe tests (no batch executed)."""
    key_dist = np.ones(job.cfg.num_clusters)
    local = np.tile(key_dist / job.cfg.num_slots, (job.cfg.num_slots, 1))
    return job._plan(local, key_dist, 128)


class TestSlowdownDirection:
    """ISSUE 5 bugfix pin: a 2x slowdown factor yields 2x measured seconds
    (and hence ~0.5x estimated speed) on BOTH timing paths."""

    def test_measured_path_two_x_factor_doubles_seconds(self):
        t = mt.WaveTimings.empty(3, 2)
        t.record(0, [1.0, 1.0, 1.0])
        t.record(1, [0.5, 0.5, 0.5])
        t.slot_work = np.full(3, 6.0)
        _, base = t.observation(None)
        _, faulted = t.observation(np.asarray([1.0, 2.0, 1.0]))
        assert np.allclose(faulted / base, [1.0, 2.0, 1.0])

    def test_synthetic_path_two_x_factor_halves_speed(self):
        job = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=4, num_clusters=16, scheduler="bss",
            estimate_speeds=True, speed_ewma=1.0), backend="vmap")
        job.set_slot_slowdown(1, 2.0)
        job.run(_batch(0, 4, K=256, key_mod=97))
        sp = job.speed_estimator.speeds()
        # synthetic rate_j = work/(work*factor) = 1/factor exactly
        assert sp[1] / sp[0] == pytest.approx(0.5)
        assert sp[1] == sp.min()

    def test_both_paths_agree_on_direction(self):
        """The measured observation and the synthetic model move the SAME
        way for the same factor (the old code had them inverted)."""
        # measured: factor 2 doubles seconds -> rate halves
        t = mt.WaveTimings.empty(2, 1)
        t.record(0, [1.0, 1.0])
        t.slot_work = np.asarray([4.0, 4.0])
        work, secs = t.observation(np.asarray([1.0, 2.0]))
        measured_ratio = (work[1] / secs[1]) / (work[0] / secs[0])
        # synthetic: same factor through the job's model
        job = MapReduceJob(lambda s: s, MapReduceConfig(
            num_slots=2, num_clusters=8, scheduler="bss",
            estimate_speeds=True, speed_ewma=1.0), backend="vmap")
        job.set_slot_slowdown(1, 2.0)
        job.run(_batch(0, 2, K=128, key_mod=7))
        sp = job.speed_estimator.speeds()
        synthetic_ratio = sp[1] / sp[0]
        assert measured_ratio == pytest.approx(0.5)
        assert synthetic_ratio == pytest.approx(0.5)
