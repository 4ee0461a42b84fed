"""Pallas TPU kernel: paged DistAttention MicroAttention (decode).

One query token per request attends over this rank's *local* slice of the
paged KV pool, selected by a scalar-prefetched block table, producing the
unnormalized MicroAttention partial ``(o, m, l)`` (paper Eq. 2). Partials
from all ranks merge with collectives (``repro.core.distattn``).

TPU mapping:
  grid = (R, MB): requests x local-table slots; MB is the innermost,
  sequential dimension so the online-softmax accumulator lives in VMEM
  scratch across slots.
  BlockSpec prefetches pool block ``table[r, j]`` directly from HBM into
  VMEM — the kernel never touches blocks that are not in the table (and
  ``pl.when`` skips -1 slots entirely).
  Tiles: KV block (bs, D) with bs=block_size and D padded to a lane
  multiple of 128 by the ops.py wrapper — (q @ k^T) is a [G, D] x
  [D, bs] MXU matmul per kv-head group, (p @ v) is [G, bs] x [bs, D].
  fp32 accumulation throughout.
  The per-head statistics m and l leave the kernel as [R, 1, H] with
  (1, 1, H) blocks: Mosaic requires a block's last two dims to be
  multiples of (8, 128) or equal to the array's, and a (1, H) block of
  an [R, H] array is neither.
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
            *, bs: int, K: int, G: int, scale: float, mb: int):
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    block_id = table_ref[r, j]

    @pl.when(block_id >= 0)
    def _compute():
        # Valid-token limit: only the request's LAST local slot is partial.
        limit = jnp.where(j == nblk_ref[r] - 1, tail_ref[r], bs)
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
                 < limit)                                    # [1, bs]
        for kh in range(K):                                  # unrolled
            qk = q_ref[0, kh * G:(kh + 1) * G, :].astype(jnp.float32)
            kb = k_ref[0, :, kh, :].astype(jnp.float32)      # [bs, D]
            vb = v_ref[0, :, kh, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qk, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, bs]
            s = jnp.where(valid, s, NEG_INF)
            m_blk = jnp.max(s, axis=-1)                      # [G]
            m_old = m_s[0, kh * G:(kh + 1) * G]
            m_new = jnp.maximum(m_old, m_blk)
            alpha = jnp.where(jnp.isneginf(m_old), 0.0,
                              jnp.exp(m_old - m_new))
            p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0,
                                      m_new)[:, None])
            p = jnp.where(valid, p, 0.0)                     # [G, bs]
            l_new = l_s[0, kh * G:(kh + 1) * G] * alpha + jnp.sum(p, -1)
            pv = jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, D]
            acc[kh * G:(kh + 1) * G, :] = (
                acc[kh * G:(kh + 1) * G, :] * alpha[:, None] + pv)
            m_s[0, kh * G:(kh + 1) * G] = m_new
            l_s[0, kh * G:(kh + 1) * G] = l_new

    @pl.when(j == mb - 1)
    def _finalize():
        o_ref[0] = acc[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def paged_micro_attention_kernel(
    q: jax.Array,          # [R, H, D]
    pool_k: jax.Array,     # [NB, bs, K, D]
    pool_v: jax.Array,
    table: jax.Array,      # [R, MB] int32 (-1 padded, sequence order)
    nblk: jax.Array,       # [R] int32 valid slots per request
    tail_len: jax.Array,   # [R] int32 valid tokens in last local slot
    *,
    scale: float,
    interpret: bool,
):
    """Returns (o [R, H, D], m [R, 1, H], l [R, 1, H]), all float32."""
    R, H, D = q.shape
    NB, bs, K, _ = pool_k.shape
    MB = table.shape[1]
    G = H // K

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, MB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda r, j, t, n, tl: (r, 0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda r, j, t, n, tl: (jnp.maximum(t[r, j], 0),
                                                 0, 0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda r, j, t, n, tl: (jnp.maximum(t[r, j], 0),
                                                 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda r, j, t, n, tl: (r, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda r, j, t, n, tl: (r, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda r, j, t, n, tl: (r, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, K=K, G=G, scale=scale, mb=MB)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, H, D), jnp.float32),
            jax.ShapeDtypeStruct((R, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((R, 1, H), jnp.float32),
        ],
        interpret=interpret,
    )(table, nblk, tail_len, q, pool_k, pool_v)
