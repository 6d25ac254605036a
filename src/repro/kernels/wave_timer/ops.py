"""Public wave-timer ops: jit-safe per-device tick stamps + their unit.

Two ops, one per ordering constraint the measured executor needs:

* ``stamp_through(primary, *anchors)`` → ``(primary, ticks)`` — the op
  the executor brackets waves with. The stamp is pinned by **true
  buffer dependencies on both sides**: it *consumes* every anchor (it
  cannot fire before the previous wave's outputs exist) and *produces*
  the very buffer the next wave's reduce reads (the scheduler cannot
  defer it past the compute it precedes). This matters: XLA:CPU's
  scheduler places instructions as late as their consumers allow, and
  neither ``optimization_barrier`` nor a value-anchored "pure" callback
  constrains it (a pure callback may even be *duplicated*, stamping a
  second time at some arbitrary later point) — both failure modes were
  observed, which is why the pass-through design exists. The primary is
  returned bit-identically.
* ``read_ticks(*anchors)`` → ``(2,)`` uint32 (lo, hi) stamp — the
  anchor-only flavour for calibration and telemetry, where ordering
  only needs to follow completed host-visible steps.

Both are exactly-once (``io_callback`` on the CPU path — effectful, so
never duplicated or dropped), safe anywhere in a jitted /
``shard_map``-ed program; under ``shard_map`` every shard stamps its
*own* device clock.

Backend resolution (process-wide, probed once per call site — cheap):

* ``"device"``  — compiled Pallas kernels (copy + cycle-counter stamp).
  Requires a toolchain primitive
  (:func:`repro.kernels.wave_timer.wave_timer.device_tick_primitive`)
  and compiled (non-interpret) kernels; calibrated on first use.
* ``"callback"`` — the interpret/CPU fallback: a per-shard
  ``perf_counter_ns`` host callback (unit exactly 1e-9 s/tick). Correct
  on CPU, where every "device" is a host thread; on a real accelerator a
  host callback would fence the stream, so it is *not* offered there.
* ``"none"``    — no usable tick source (e.g. a TPU whose toolchain has
  no counter primitive). ``available()`` is False and the measured
  executor falls back to host-fenced timing
  (:func:`repro.core.mesh_timing.shard_ready_seconds`).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import io_callback

from repro.analysis import allowlist as _allowlist
from repro.kernels.wave_timer import calibration as _cal
from repro.kernels.wave_timer import ref as wt_ref
from repro.kernels.wave_timer import wave_timer as _wt

__all__ = ["backend", "available", "read_ticks", "stamp_through",
           "combine_ticks", "tick_calibration", "force_backend"]

# Test/bench override: force_backend("none") drills the host-fenced
# fallback without uninstalling the tick source.
_FORCED: Optional[str] = None

_TICK_SHAPE = jax.ShapeDtypeStruct((2,), jnp.uint32)

combine_ticks = wt_ref.combine_ticks


def backend() -> str:
    """Resolve the tick backend: ``"device"`` | ``"callback"`` | ``"none"``."""
    if _FORCED is not None:
        return _FORCED
    platform = jax.default_backend()
    if platform == "tpu" and _wt.device_tick_primitive() is not None:
        return "device"
    if platform == "cpu":
        return "callback"
    return "none"


def available() -> bool:
    """True when on-device (or CPU-fallback) tick stamps can be read."""
    return backend() != "none"


# The wave-timer stamps are the engine's ONE sanctioned host callback:
# registered with the contract analyzer's allowlist at the definition,
# so `repro.analysis --check determinism` certifies that nothing else in
# a traced phase-B program crosses the host boundary.
@_allowlist.allow_callback
def _host_stamp(*_anchors) -> np.ndarray:
    """The callback body: one host perf_counter_ns stamp as (lo, hi) words."""
    return wt_ref.read_ticks_ref()


@_allowlist.allow_callback
def _host_stamp_through(primary, *_anchors):
    """Callback body: pass ``primary`` through untouched + one stamp."""
    return np.asarray(primary), wt_ref.read_ticks_ref()


def read_ticks(*anchors) -> jax.Array:
    """One per-device tick stamp ``(2,)`` uint32, ordered after ``anchors``.

    Exactly-once and ordered *after* its anchors (it consumes them), but
    a scheduler may still defer it until its ticks output is needed — use
    :func:`stamp_through` to pin a stamp *before* a computation. Raises
    ``RuntimeError`` when no backend is available — callers gate on
    :func:`available` and fall back to host-fenced timing instead.
    """
    b = backend()
    if b == "device":
        a = anchors[0] if anchors else jnp.float32(0)
        for extra in anchors[1:]:           # fold every anchor into the dep
            a = a + jnp.asarray(extra, jnp.float32).reshape(-1)[0] * 0
        return _wt.read_ticks_pallas(a, interpret=False)
    if b == "callback":
        if not anchors:
            anchors = (jnp.float32(0),)
        return io_callback(_host_stamp, _TICK_SHAPE, *anchors,
                           ordered=False)  # analysis: allow-callback
    raise RuntimeError("no wave-timer tick backend on this platform")


def stamp_through(primary, *anchors):
    """Stamp the device clock *between* two computations, exactly once.

    Returns ``(primary, ticks)`` where ``primary`` comes back
    bit-identical. The stamp consumes every ``anchor`` (true reads — it
    cannot execute before they exist) and produces the returned
    ``primary`` buffer — feed that to the downstream computation and the
    stamp cannot be deferred past it either. This double-sided pinning is
    what makes in-program wave timing honest; see the module docstring
    for why weaker orderings (``optimization_barrier``, pure callbacks)
    are not enough.
    """
    b = backend()
    if b == "device":
        return _wt.stamp_through_pallas(primary, *anchors, interpret=False)
    if b == "callback":
        # Only the leading row crosses the host (bytes, not buffers): the
        # callback passes ``primary[:1]`` through verbatim and the result
        # is stitched back with a device-side concatenate. Every consumer
        # of the stitched array now depends on the callback's output, so
        # the ordering is as strong as passing the whole buffer — without
        # round-tripping it through host memory.
        head = jax.lax.slice_in_dim(primary, 0, 1, axis=0)
        shapes = (jax.ShapeDtypeStruct(head.shape, head.dtype), _TICK_SHAPE)
        passed, ticks = io_callback(  # analysis: allow-callback
            _host_stamp_through, shapes, head, *anchors, ordered=False)
        if primary.shape[0] <= 1:
            return passed, ticks
        rest = jax.lax.slice_in_dim(primary, 1, primary.shape[0], axis=0)
        return jax.lax.concatenate([passed, rest], 0), ticks
    raise RuntimeError("no wave-timer tick backend on this platform")


class force_backend:
    """Context manager pinning :func:`backend` (tests / fallback drills)."""

    def __init__(self, name: Optional[str]):
        if name not in (None, "device", "callback", "none"):
            raise ValueError(f"unknown wave-timer backend {name!r}")
        self._name = name
        self._prev: Optional[str] = None

    def __enter__(self):
        global _FORCED
        self._prev, _FORCED = _FORCED, self._name
        return self

    def __exit__(self, *exc):
        global _FORCED
        _FORCED = self._prev
        return False


_CALIBRATION_CACHE: dict = {}


def tick_calibration() -> _cal.TickCalibration:
    """The current backend's tick unit (calibrated once for ``"device"``)."""
    b = backend()
    if b == "callback":
        return _cal.HOST_NS
    if b == "device":
        cached = _CALIBRATION_CACHE.get(b)
        if cached is None:
            def _read() -> int:
                words = jax.device_get(read_ticks(jnp.float32(time.monotonic())))
                return int(wt_ref.combine_ticks(np.asarray(words)))
            cached = _CALIBRATION_CACHE[b] = _cal.calibrate(_read)
        return cached
    raise RuntimeError("no wave-timer tick backend to calibrate")
