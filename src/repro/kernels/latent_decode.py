"""Pallas TPU kernel: paged latent (MLA) MicroAttention for decode.

Latent attention caches ONE row per token per layer, ``[c_kv | k_pe]``
(``W = kv_lora_rank + qk_rope_head_dim`` wide), that every head reads.
The query arrives absorbed into that space: ``q_nope`` times each head's
``W_UK`` beside the rotated ``q_pe``, ``[R, H, W]``. A head's score with
a token is then one dot product with the row, and its value is the row's
first ``kv_lora_rank`` entries, so the partial ``(o_latent [H, r], m, l)``
(paper Eq. 2) merges by the same log-sum-exp as the dense one (Eq. 3);
``W_UV`` and the output projection run once, on the owner, after the
merge.

The kernel takes the whole stacked pool ``[L, NB, bs, W]`` (in HBM,
``pl.ANY``) and the layer as a scalar: a layer's slice is a DMA offset,
never a copy. Table contract as in ``micro_attn_decode``: a row's valid
slots are the prefix ``[0, nblk)``, ``tail`` tokens of its last slot are
valid, ``nblk == 0`` gives ``o = 0, m = -inf, l = 0``.

TPU mapping (the design of ``micro_attn_decode``):
  grid = (R,): one sequential step per request. A step loops over its
  own ``ceil(nblk / pb)`` tiles of ``pb`` table slots only: no grid
  step, DMA or compute for -1 padding. Each slot's ``[bs, W]`` block is
  copied by its own async DMA into a double-buffered VMEM tile
  ``[2, pb, bs, W]``; tile t+1's copies start before tile t is computed.
  A tile is one ``[pb*bs, W]`` slab: the scores of all H heads are one
  ``[H, W] x [W, pb*bs]`` matmul and the values one
  ``[H, pb*bs] x [pb*bs, r]`` matmul, both in the pool's dtype with
  float32 accumulation; scores past ``(nblk-1)*bs + tail`` are masked.
  m and l leave as ``[R, 1, H]`` blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(layer_ref, table_ref, nblk_ref, tail_ref,   # scalar prefetch
            q_ref, pool_hbm,                            # q in VMEM; pool HBM
            o_ref, m_ref, l_ref,                        # VMEM outputs
            buf, sem,                                   # scratch
            *, bs: int, pb: int, rank: int, scale: float):
    b = pl.program_id(0)
    layer = layer_ref[0]
    n = nblk_ref[b]
    H, C = q_ref.shape[1], pb * bs
    ntiles = (n + pb - 1) // pb

    @pl.when(b == 0)
    def _clear():
        # Slots past a last tile's valid ones keep stale blocks; clearing
        # once keeps uninitialized VMEM (maybe NaN) out of the p @ row sum.
        buf[...] = jnp.zeros_like(buf)

    def copy(slot, i, blk):
        return pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                     buf.at[slot, i], sem.at[slot])

    def each_slot(t, fn):                   # fn(i) for tile t's valid slots
        def body(i, c):
            fn(i)
            return c
        lax.fori_loop(0, jnp.minimum(pb, n - t * pb), body, 0)

    def fetch(t, slot):
        each_slot(t, lambda i: copy(slot, i,
                                    table_ref[b, t * pb + i]).start())

    def wait(t, slot):
        each_slot(t, lambda i: copy(slot, 0, 0).wait())

    @pl.when(n > 0)
    def _first():
        fetch(0, 0)

    q = q_ref[0].astype(buf.dtype)                   # [H, W]
    col = lax.broadcasted_iota(jnp.int32, (H, C), 1)
    limit = (n - 1) * bs + tail_ref[b]

    def tile(t, carry):
        acc, m, l = carry
        slot = t % 2

        @pl.when(t + 1 < ntiles)
        def _next():
            fetch(t + 1, 1 - slot)

        wait(t, slot)
        rows = buf[slot].reshape(C, -1)              # [C, W]
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(t * C + col < limit, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m - shift)                   # 0 while m is -inf
        p = jnp.exp(s - shift)                       # masked scores -> 0
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(rows.dtype), rows[:, :rank],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return acc * alpha + pv, m_new, l

    acc, m, l = lax.fori_loop(
        0, ntiles, tile,
        (jnp.zeros((H, rank), jnp.float32),
         jnp.full((H, 1), NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32)))
    o_ref[0] = acc
    m_ref[0] = m.T
    l_ref[0] = l.T


def paged_latent_attention_kernel(
    q: jax.Array,          # [R, H, W]  absorbed queries
    pool: jax.Array,       # [L, NB, bs, W]  the whole stacked latent pool
    layer: jax.Array,      # [1] int32
    tables: jax.Array,     # [R, MB] int32 (valid prefix, -1 padded)
    nblk: jax.Array,       # [R] int32 valid slots per row
    tails: jax.Array,      # [R] int32 valid tokens in the last slot
    *,
    rank: int,
    scale: float,
    tile_blocks: int,
    interpret: bool,
):
    """Returns (o [R, H, rank], m [R, 1, H], l [R, 1, H]), float32;
    ``rank`` leading entries of a row are its value, ``tile_blocks``
    table slots make one compute tile."""
    R, H, W = q.shape
    _, _, bs, _ = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_blocks, bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, pb=tile_blocks, rank=rank,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, H, rank), jnp.float32),
            jax.ShapeDtypeStruct((R, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((R, 1, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(layer, tables, nblk, tails, q, pool)
