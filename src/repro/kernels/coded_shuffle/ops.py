"""Public wrapper for the coded-shuffle XOR kernel + payload word packing.

``xor_words`` is the multicast encode *and* decode of the coded shuffle:
senders XOR the two destination slabs of a multicast pair into one
packet; receivers XOR the packet against the slab they reconstruct from
locally-replicated map data. Two execution paths behind one signature:

* ``use_kernel=True``  — the Pallas kernel (interpret-mode on CPU);
* ``use_kernel=False`` — the pure-jnp fallback, identical bits, safe
  under ``jax.vmap`` (the engine's CPU backend maps slots with vmap,
  where a pallas_call has no batching rule).

The packing helpers give the engine a single word-level wire format:
float payloads (f32/bf16) and quantized bytes (int8/fp8) are bit-cast
into int32 words, XOR-combined, and bit-cast back — XOR on the word view
is XOR on the underlying payload bits, so decode is exact for every
payload dtype.

Identical-sort wire contract (Coded MapReduce, arXiv 1512.01625): a
packet only decodes because sender and receiver rebuild the *same* slab
from replicated records — every sort that shapes this wire (the engine's
ragged counting-sort spill and the receiver's ``(src, j)`` re-order in
``core.mapreduce``) must be explicitly stable, never stable-by-default.
``repro.analysis --check determinism`` certifies this statically on the
traced coded programs; see docs/ANALYSIS.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels as _k
from repro.kernels.coded_shuffle.coded_shuffle import xor_words_pallas
from repro.kernels.coded_shuffle.ref import xor_words_ref

_WORD = jnp.int32
_BYTES_PER_WORD = 4


def xor_words(a: jax.Array, b: jax.Array, *, use_kernel: bool = True) -> jax.Array:
    """Elementwise ``a ^ b`` over (N, W) int32/uint32 word slabs."""
    if use_kernel:
        return xor_words_pallas(a, b, interpret=_k.interpret())
    return xor_words_ref(a, b)


def packed_width(v_dim: int, dtype) -> int:
    """Words per row when packing ``(N, v_dim)`` of ``dtype`` into int32."""
    itemsize = jnp.dtype(dtype).itemsize
    group = _BYTES_PER_WORD // itemsize
    return -(-v_dim // group)


def pack_payload_words(x: jax.Array) -> jax.Array:
    """Bit-cast an ``(N, V)`` payload into ``(N, W)`` int32 words.

    Lanes are grouped ``4 // itemsize`` at a time (f32 → 1 lane/word,
    bf16 → 2, int8/fp8 → 4); ``V`` is zero-padded up to a whole group so
    padding bits are zero and XOR-neutral. Exact round-trip via
    :func:`unpack_payload_words` for every supported dtype.
    """
    n, v = x.shape
    itemsize = jnp.dtype(x.dtype).itemsize
    if itemsize > _BYTES_PER_WORD:
        raise ValueError(f"payload dtype {x.dtype} wider than a word")
    group = _BYTES_PER_WORD // itemsize
    pad = (-v) % group
    if pad:
        x = jnp.concatenate([x, jnp.zeros((n, pad), x.dtype)], axis=1)
    if group == 1:
        return jax.lax.bitcast_convert_type(x, _WORD)
    grouped = x.reshape(n, (v + pad) // group, group)
    return jax.lax.bitcast_convert_type(grouped, _WORD)


def unpack_payload_words(words: jax.Array, dtype, v_dim: int) -> jax.Array:
    """Invert :func:`pack_payload_words` back to ``(N, v_dim)`` of ``dtype``."""
    n, w = words.shape
    itemsize = jnp.dtype(dtype).itemsize
    group = _BYTES_PER_WORD // itemsize
    if w != packed_width(v_dim, dtype):
        raise ValueError(
            f"word slab width {w} does not match v_dim={v_dim} of {dtype}"
        )
    x = jax.lax.bitcast_convert_type(words, dtype)
    if group > 1:
        x = x.reshape(n, w * group)
    return x[:, :v_dim]
