"""Pallas tick kernel — stamp a per-device clock inside a jitted program.

The measured phase-B executor (``repro.core.mapreduce``) wants one
monotone counter sample *per device, at a chosen point of the program's
data flow* — immediately before and after each §4.4 wave's shard-local
reduce — without fencing the program into per-wave dispatches. That is a
kernel-level concern: the stamp must execute on the device, ordered by
data dependencies only.

Tick source resolution (compile-time, per process):

* **Device cycle counter** — when the installed Pallas/Mosaic toolchain
  exposes one (probed by name in :func:`device_tick_primitive`; jax
  generations disagree on where it lives, and the 0.4.x line this
  container ships has none). The kernel writes the counter's (lo, hi)
  uint32 words — see :mod:`repro.kernels.wave_timer.ref` for the format —
  and :mod:`.calibration` measures its seconds-per-tick once.
* **Interpret / CPU fallback** — the kernel body degrades to a host
  ``perf_counter_ns`` callback (per *virtual* device: under
  ``shard_map`` each shard's program invokes its own callback, so forced
  host devices still get per-slot stamps). Seconds-per-tick is exactly
  1e-9, no calibration needed.

Two kernels (the "kernel pair"):

* :func:`read_ticks_pallas` — a (1,) anchor in, a (2,) word pair out.
  The anchor is the ordering handle: its *value* is ignored, but the
  stamp cannot execute before whatever computed it.
* :func:`stamp_through_pallas` — copy a primary buffer verbatim AND
  stamp the clock in the same kernel execution. The copy is what pins
  the stamp *before* downstream compute: the consumer reads the
  kernel's output buffer, so no scheduler can defer the stamp past it
  (an anchor alone only orders the stamp *after* its inputs — see
  ``ops.stamp_through`` for the full ordering story).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis import allowlist as _allowlist
from repro.kernels.wave_timer import ref as wt_ref

__all__ = ["device_tick_primitive", "read_ticks_pallas",
           "stamp_through_pallas"]


# Interpret-mode kernels stamp the host clock through this one body —
# registered with the contract analyzer's allowlist (the jaxpr-level
# declaration) and marked at each call site (the source-level one).
@_allowlist.allow_callback
def _host_ticks(_anchor):
    """Callback body: one host perf_counter_ns stamp as (lo, hi) words."""
    return wt_ref.read_ticks_ref()

# Names a device cycle counter has gone by across Pallas-TPU generations.
# Probed, never imported directly: absence means "no device counter" and
# the caller falls back (CPU callback ticks, or host-fenced timing).
_DEVICE_TICK_CANDIDATES = ("cycle_count", "read_cycle_count", "clock")


def device_tick_primitive():
    """The device cycle-counter primitive, or ``None`` on this toolchain."""
    try:
        from jax.experimental.pallas import tpu as pltpu
    except ImportError:                     # pragma: no cover - no pallas tpu
        return None
    for name in _DEVICE_TICK_CANDIDATES:
        fn = getattr(pltpu, name, None)
        if fn is not None:
            return fn
    return None


def _split_counter_words(t) -> jnp.ndarray:
    """Split a counter sample into ``(2,)`` (lo, hi) uint32 words.

    Deliberately avoids 64-bit jnp lanes: without ``jax_enable_x64``,
    ``jnp.uint64`` silently canonicalizes to uint32, which would zero the
    hi word and wrap the counter every 2^32 ticks. The split stays in the
    counter's native dtype — a 64-bit counter masks/shifts losslessly, a
    32-bit counter gets an explicit zero hi word (its wrap period is then
    the genuine hardware limit; ``WaveTimings.from_ticks`` flags wrapped
    intervals as invalid).
    """
    t = jnp.asarray(t).reshape(())
    if t.dtype.itemsize == 8:
        mask = t.dtype.type(0xFFFFFFFF)
        shift = t.dtype.type(32)
        lo = (t & mask).astype(jnp.uint32)
        hi = (t >> shift).astype(jnp.uint32)
    else:
        lo = t.astype(jnp.uint32)
        hi = jnp.zeros((), jnp.uint32)
    return jnp.stack([lo, hi])


def _tick_kernel_device(anchor_ref, out_ref, *, counter):
    """Compiled body: split the device cycle counter into (lo, hi) words."""
    del anchor_ref                          # ordering handled by pallas_call dep
    out_ref[...] = _split_counter_words(counter())


def _tick_kernel_host(anchor_ref, out_ref):
    """Interpret body: stamp the host clock via a pure callback.

    Interpret mode evaluates the kernel body as ordinary traced jax, so a
    host callback is legal here; a compiled TPU kernel could never take
    this path (``read_ticks_pallas`` refuses the combination).
    """
    out_ref[...] = jax.pure_callback(  # analysis: allow-callback
        _host_ticks,
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        anchor_ref[0],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def read_ticks_pallas(anchor, *, interpret: bool) -> jax.Array:
    """One tick stamp as ``(2,)`` uint32 (lo, hi) words.

    ``anchor`` is any scalar/array whose *computation* must precede the
    stamp — the kernel consumes it as input so the stamp cannot be hoisted
    above it. With ``interpret=False`` a device cycle counter is required
    (``RuntimeError`` when the toolchain has none).
    """
    counter = device_tick_primitive()
    if not interpret and counter is None:
        raise RuntimeError(
            "no device cycle-counter primitive in this Pallas toolchain; "
            "wave_timer ticks are interpret/CPU-only here"
        )
    kernel = (_tick_kernel_host if counter is None
              else functools.partial(_tick_kernel_device, counter=counter))
    a = jnp.asarray(anchor, jnp.float32).reshape(-1)[:1]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2,), jnp.uint32),
        interpret=interpret,
    )(a)


def _stamp_through_kernel_device(primary_ref, *rest, counter):
    """Compiled body: verbatim copy of the primary + one counter stamp."""
    *_anchors, out_ref, tick_ref = rest
    out_ref[...] = primary_ref[...]
    tick_ref[...] = _split_counter_words(counter())


def _stamp_through_kernel_host(primary_ref, *rest):
    """Interpret body: verbatim copy + a host-clock callback stamp."""
    anchors = rest[:-2]
    out_ref, tick_ref = rest[-2:]
    out_ref[...] = primary_ref[...]
    a = anchors[0][0] if anchors else primary_ref[0]
    tick_ref[...] = jax.pure_callback(  # analysis: allow-callback
        _host_ticks,
        jax.ShapeDtypeStruct((2,), jnp.uint32), a,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def stamp_through_pallas(primary, *anchors, interpret: bool):
    """Copy ``primary`` bit-identically and stamp the clock in one kernel.

    Returns ``(primary_copy, ticks)``. ``anchors`` are additional inputs
    the stamp must wait for (their values are ignored). With
    ``interpret=False`` a device cycle counter is required.
    """
    counter = device_tick_primitive()
    if not interpret and counter is None:
        raise RuntimeError(
            "no device cycle-counter primitive in this Pallas toolchain; "
            "wave_timer ticks are interpret/CPU-only here"
        )
    kernel = (_stamp_through_kernel_host if counter is None
              else functools.partial(_stamp_through_kernel_device,
                                     counter=counter))
    flat_anchors = tuple(
        jnp.asarray(a, jnp.float32).reshape(-1)[:1] for a in anchors
    )
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(primary.shape, primary.dtype),
                   jax.ShapeDtypeStruct((2,), jnp.uint32)),
        interpret=interpret,
    )(primary, *flat_anchors)
