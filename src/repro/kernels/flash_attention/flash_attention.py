"""Flash attention (online-softmax) Pallas kernel, causal + GQA aware.

Role in the OS4M port: attention is the dominant FLOP producer of the
assigned LM architectures; keeping train_4k / prefill_32k *compute-bound*
(§Roofline) requires never materialising the (T, S) score matrix in HBM.

TPU design
----------
* grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is innermost
  and sequential ("arbitrary"), carrying the online-softmax state in VMEM
  scratch across kv visits:
    acc (block_q, head_dim) f32 — unnormalised output accumulator
    m, l (block_q, 128) f32     — running row max / normaliser
      (lane-replicated to match the (8, 128) vreg tile; column 0 is the
      value, replication keeps broadcasts register-shaped)
* Per program: q-tile (block_q, d) and kv-tiles (block_k, d) live in VMEM;
  the two matmuls (q @ k^T and p @ v) hit the MXU with d and block_k both
  multiples of 128.
* GQA is handled in the BlockSpec index maps: query head ``h`` reads kv
  head ``h // (Hq // Hkv)`` — no kv replication in HBM.
* Causality is block-sparse: kv blocks entirely above the diagonal are
  skipped with ``pl.when`` (no MXU work, no HBM traffic beyond the slab
  prefetch), which halves causal FLOPs. The diagonal block applies the
  triangular mask; key padding is masked via absolute indices.

Default tiles (block_q = block_k = 512, d = 128): q/k/v slabs 128 KB each
+ one (512, 512) f32 score tile = 1 MB — comfortable VMEM residency with
double buffering. ``block_k`` is the knob that trades VMEM for fewer
sequential kv steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, causal: bool, sm_scale: float,
    kv_len: int, num_kv_blocks: int, q_offset: int,
):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ``q_offset`` aligns queries to the *end* of the kv axis (suffix
    # alignment: query i sits at absolute position q_offset + i), which is
    # what chunked prefill against a KV cache needs.
    q0 = qb * block_q + q_offset
    k0 = kb * block_k

    # Causal block-sparsity: skip kv blocks strictly above the diagonal.
    run = (k0 <= q0 + block_q - 1) if causal else True

    @pl.when(run)
    def _work():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        # Key-padding mask (absolute) + causal mask on the diagonal band.
        kv_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_idx < kv_len
        if causal:
            q_idx = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask &= kv_idx <= q_idx
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                       # (bq,)
        m_cur = jnp.max(s, axis=1)                 # (bq,)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # rescale factor
        p = jnp.exp(s - m_new[:, None])            # (bq, bk)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = l_ref[...] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        norm = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0, 0] = (acc_ref[...] * norm[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "sm_scale"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    block_q = min(block_q, t)
    block_k = min(block_k, s)
    pad_q = (-t) % block_q
    pad_k = (-s) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    tq, sk = t + pad_q, s + pad_k
    grid = (b, hq, tq // block_q, sk // block_k)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            block_q=block_q, block_k=block_k, causal=causal,
            sm_scale=float(sm_scale), kv_len=s, num_kv_blocks=grid[3],
            q_offset=s - t,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qb, kb: (b_, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, qb, kb: (b_, h // group, kb, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, qb, kb: (b_, h // group, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qb, kb: (b_, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :t, :]
