"""Mesh-parallel DistAttention: MicroAttention partials merged by collectives.

This is the paper's Eq. 2-3 mapped onto TPU collectives inside
``shard_map``: every rank computes a MicroAttention partial over whatever
KV blocks it *locally* holds (possibly none — empty partials are the monoid
identity and merge away), then the partials are reduced with one ``pmax``
and two ``psum``s over the mesh axes that can hold KV.  Per-step traffic is
the query + per-head scalars + one value vector — never the KVCache.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.online_softmax import finalize, micro_attention_decode

AxisNames = Union[str, Sequence[str]]


def merge_over_axes(o: jax.Array, m: jax.Array, l: jax.Array,
                    axis_names: AxisNames):
    """Collective LSE-merge of per-rank partials (paper Eq. 3).

    Must be called inside shard_map. Returns the *normalized* output.
    Traffic: pmax(m) + psum(l') + psum(o') = (2 * |m| + |o|) elements.
    """
    m_g = jax.lax.pmax(m, axis_names)
    scale = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_g))
    l_g = jax.lax.psum(l * scale, axis_names)
    o_g = jax.lax.psum(o * scale[..., None], axis_names)
    return finalize(o_g, l_g)


def gather_local_kv(pool_k: jax.Array, pool_v: jax.Array,
                    local_table: jax.Array):
    """Materialize [B, S_local, K, D] KV from a paged pool.

    pool_k/pool_v: [num_blocks_local, block_size, K, D] — this rank's pool.
    local_table:   [B, max_local_blocks] int32 — local block ids, -1 = none.

    Invalid entries gather block 0 and are masked by the caller via
    ``local_mask_from_table``.
    """
    nb, bs, K, D = pool_k.shape
    safe = jnp.maximum(local_table, 0)
    k = pool_k[safe].reshape(local_table.shape[0], -1, K, D)
    v = pool_v[safe].reshape(local_table.shape[0], -1, K, D)
    return k, v


def local_mask_from_table(local_table: jax.Array, block_size: int,
                          last_block_len: jax.Array | None = None):
    """[B, max_local_blocks*block_size] bool validity mask for gathered KV.

    ``last_block_len``: optional [B] — number of valid tokens in each
    request's final (partially filled) block; the fill block id must be
    the lexicographically-last valid entry of the row.
    """
    B, MB = local_table.shape
    valid_block = (local_table >= 0)
    mask = jnp.repeat(valid_block, block_size, axis=1)
    if last_block_len is not None:
        # Positions within each block.
        within = jnp.tile(jnp.arange(block_size), MB)[None, :]
        n_valid = valid_block.sum(axis=1)                       # [B]
        block_idx = jnp.repeat(jnp.arange(MB)[None, :], B, 0)
        block_idx = jnp.repeat(block_idx, block_size, axis=1)
        is_last = block_idx == (n_valid - 1)[:, None]
        mask = mask & jnp.where(is_last, within < last_block_len[:, None], True)
    return mask


def distattn_decode_paged(
    q: jax.Array,             # [B, H, D] (replicated or per-rank batch slice)
    pool_k: jax.Array,        # [NB_local, bs, K, D]
    pool_v: jax.Array,
    local_table: jax.Array,   # [B, MB_local] int32, -1 padded
    last_block_len: jax.Array,  # [B] tokens valid in final local block
    axis_names: AxisNames,
    *,
    scale: float | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
):
    """Full paged DistAttention decode step for one layer, inside shard_map.

    Each rank attends over its local pool blocks (Pallas kernel or jnp
    reference), then partials merge across ``axis_names``.
    """
    bs = pool_k.shape[1]
    if backend == "pallas":
        from repro.kernels.ops import paged_micro_attention
        o, m, l = paged_micro_attention(q, pool_k, pool_v, local_table,
                                        last_block_len, scale=scale,
                                        interpret=interpret,
                                        backend="pallas")
    else:
        k, v = gather_local_kv(pool_k, pool_v, local_table)
        mask = local_mask_from_table(local_table, bs, last_block_len)
        o, m, l = micro_attention_decode(q, k, v, mask, scale=scale)
    out = merge_over_axes(o, m, l, axis_names)
    return out.astype(q.dtype)
