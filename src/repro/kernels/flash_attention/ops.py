"""Public wrappers for attention: flash kernel (prefill/train) + decode path.

``flash_attention`` is the Pallas kernel. ``decode_attention`` is the
one-new-token path: at q_len = 1 the op is HBM-bandwidth-bound (stream the
KV cache once); a blocked MXU kernel buys nothing, so it is expressed as
einsums XLA fuses into a single pass. Both share the oracle in ref.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels as _k
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 512, block_k: int = 512):
    """(B, Hq, T, D) x (B, Hkv, S, D)^2 -> (B, Hq, T, D)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=_k.interpret(),
    )


def decode_attention(q, k_cache, v_cache, cache_len, *, sm_scale: float | None = None):
    """Single-step attention against a (B, Hkv, S, D) cache; q is (B, Hq, 1, D).

    ``cache_len`` may be a scalar or (B,) vector of valid cache lengths.
    """
    b, hq, _, d = q.shape
    _, hkv, s, _ = k_cache.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, group, d)
    logits = jnp.einsum(
        "bhgd,bhsd->bhgs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * sm_scale
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(cache_len), (b,))[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)
