"""Pallas TPU kernel: paged DistAttention MicroAttention (prefill chunk).

A whole chunk of C query rows (positions [t0, t0+C)) attends over this
rank's slice of the paged KV pool — the already-written prefix [0, t0)
addressed by ONE shared, scalar-prefetched block table. Because every
addressed token precedes every chunk query, no causal mask is needed
inside the kernel: validity is purely the table (-1 slots skipped) and
the tail length of the final block. The unnormalized partial
``(o, m, l)`` (paper Eq. 2) LSE-merges with the chunk-internal causal
partial and the other ranks' partials (paper Eq. 3), which is what makes
streaming paged prefill equal dense full-prefix attention.

TPU mapping:
  grid = (MB,): local-table slots, sequential, so the online-softmax
  accumulator for ALL C queries lives in VMEM scratch across slots.
  BlockSpec prefetches pool block ``table[j]`` straight from HBM into
  VMEM; blocks not in the table are never touched and -1 slots are
  skipped by ``pl.when``.
  The wrapper lays queries out as [K * C * G, D] (kv-head-major) so each
  kv-head group is a contiguous [C*G, D] row slab: (q @ k^T) is a
  [C*G, D] x [D, bs] MXU matmul per kv head, (p @ v) is [C*G, bs] x
  [bs, D]. fp32 accumulation throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(table_ref, nblk_ref, tail_ref,          # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,                    # VMEM inputs
            o_ref, m_ref, l_ref,                    # VMEM outputs
            acc, m_s, l_s,                          # VMEM scratch
            *, bs: int, K: int, CG: int, scale: float, mb: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    block_id = table_ref[j]

    @pl.when(block_id >= 0)
    def _compute():
        # Only the prefix's LAST block can be partially written.
        limit = jnp.where(j == nblk_ref[0] - 1, tail_ref[0], bs)
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
                 < limit)                                    # [1, bs]
        for kh in range(K):                                  # unrolled
            rows = slice(kh * CG, (kh + 1) * CG)
            qk = q_ref[rows, :].astype(jnp.float32)          # [CG, D]
            kb = k_ref[0, :, kh, :].astype(jnp.float32)      # [bs, D]
            vb = v_ref[0, :, kh, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qk, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [CG, bs]
            s = jnp.where(valid, s, NEG_INF)
            m_blk = jnp.max(s, axis=-1)                      # [CG]
            m_old = m_s[0, rows]
            m_new = jnp.maximum(m_old, m_blk)
            alpha = jnp.where(jnp.isneginf(m_old), 0.0,
                              jnp.exp(m_old - m_new))
            p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0,
                                      m_new)[:, None])
            p = jnp.where(valid, p, 0.0)                     # [CG, bs]
            l_new = l_s[0, rows] * alpha + jnp.sum(p, -1)
            pv = jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [CG, D]
            acc[rows, :] = acc[rows, :] * alpha[:, None] + pv
            m_s[0, rows] = m_new
            l_s[0, rows] = l_new

    @pl.when(j == mb - 1)
    def _finalize():
        o_ref[...] = acc[...]
        m_ref[...] = m_s[...]
        l_ref[...] = l_s[...]


def paged_prefill_micro_attention_kernel(
    q: jax.Array,          # [K * CG, D] kv-head-major chunk queries
    pool_k: jax.Array,     # [NB, bs, K, D]
    pool_v: jax.Array,
    table: jax.Array,      # [MB] int32 (-1 padded, sequence order)
    nblk: jax.Array,       # [1] int32 valid slots of the shared table
    tail_len: jax.Array,   # [1] int32 valid tokens in the LAST slot
    *,
    num_kv_heads: int,
    scale: float,
    interpret: bool,
):
    KCG, D = q.shape
    NB, bs, K, _ = pool_k.shape
    assert K == num_kv_heads and KCG % K == 0
    CG = KCG // K
    MB = table.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(MB,),
        in_specs=[
            pl.BlockSpec((KCG, D), lambda j, t, n, tl: (0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda j, t, n, tl: (jnp.maximum(t[j], 0),
                                              0, 0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda j, t, n, tl: (jnp.maximum(t[j], 0),
                                              0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((KCG, D), lambda j, t, n, tl: (0, 0)),
            pl.BlockSpec((1, KCG), lambda j, t, n, tl: (0, 0)),
            pl.BlockSpec((1, KCG), lambda j, t, n, tl: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((KCG, D), jnp.float32),
            pltpu.VMEM((1, KCG), jnp.float32),
            pltpu.VMEM((1, KCG), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, K=K, CG=CG, scale=scale,
                               mb=MB)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((KCG, D), jnp.float32),
            jax.ShapeDtypeStruct((1, KCG), jnp.float32),
            jax.ShapeDtypeStruct((1, KCG), jnp.float32),
        ],
        interpret=interpret,
    )(table, nblk, tail_len, q, pool_k, pool_v)
