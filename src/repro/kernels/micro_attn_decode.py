"""Pallas TPU kernel: paged DistAttention MicroAttention (decode).

One query token per request attends over one rank's slice of the paged
KV pool, selected by a scalar-prefetched block table, producing the
unnormalized MicroAttention partial ``(o, m, l)`` (paper Eq. 2). Partials
from all ranks merge by their log-sum-exp (``repro.core.online_softmax``).

Table contract: a row's valid slots are the prefix ``[0, nblk)`` in
sequence order, the rest ``-1`` (``kvpool.build_local_tables`` and
``kvpool.prefix_tables`` build them so); ``tail`` tokens of the last
valid block are valid. A row with ``nblk == 0`` yields the empty
partial ``o = 0, m = -inf, l = 0``.

TPU mapping:
  grid = (NR, R): ranks x requests, one step per table row, sequential.
  The pools stay in HBM (``pl.ANY``). Inside a step the kernel loops
  over the row's own blocks only, ``ceil(nblk / pb)`` compute tiles of
  ``pb`` table slots: no grid step, DMA or compute for -1 padding or for
  empty rows. Each slot's (bs, K, D) block is copied by its own async
  DMA into a double-buffered VMEM tile [2, pb, bs, K, D] (K and V each);
  tile t+1's copies start before tile t is computed, and the last tile
  copies only its valid slots.
  A tile is one [pb*bs*K, D] slab (rows are (token, kv head)), so the
  scores of all H heads are one [H, D] x [D, pb*bs*K] MXU matmul and
  the values one [H, pb*bs*K] x [pb*bs*K, D] matmul; a score whose row's
  kv head differs from the column's, or whose token lies past
  ``(nblk-1)*bs + tail``, is masked to -inf. K and V feed the MXU in
  their stored dtype; scores, p, the online-softmax statistics and the
  accumulator are float32.
  m and l leave as [NR, R, 1, H] with (1, 1, 1, H) blocks: a block's
  last two dims must be multiples of (8, 128) or equal to the array's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(table_ref, nblk_ref, tail_ref,          # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm,                    # q in VMEM; pools in HBM
            o_ref, m_ref, l_ref,                    # VMEM outputs
            kbuf, vbuf, sem,                        # scratch
            *, bs: int, K: int, G: int, pb: int, scale: float):
    g, r = pl.program_id(0), pl.program_id(1)
    n = nblk_ref[g, r]
    H, C = K * G, pb * bs * K                       # heads; tile rows
    ntiles = (n + pb - 1) // pb

    @pl.when((g == 0) & (r == 0))
    def _clear():
        # Slots past a last tile's valid ones keep stale blocks; clearing
        # once keeps uninitialized VMEM (maybe NaN) out of the p @ v sum.
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(slot, i, blk):
        return (pltpu.make_async_copy(k_hbm.at[g, blk], kbuf.at[slot, i],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[g, blk], vbuf.at[slot, i],
                                      sem.at[1, slot]))

    def each_slot(t, fn):                   # fn(i) for tile t's valid slots
        def body(i, c):
            fn(i)
            return c
        lax.fori_loop(0, jnp.minimum(pb, n - t * pb), body, 0)

    def fetch(t, slot):
        def start(i):
            for cp in copies(slot, i, table_ref[g, r, t * pb + i]):
                cp.start()
        each_slot(t, start)

    def wait(t, slot):
        def done(i):
            for cp in copies(slot, 0, 0):
                cp.wait()
        each_slot(t, done)

    @pl.when(n > 0)
    def _first():
        fetch(0, 0)

    q = q_ref[0]                                    # [H, D]
    ct = jnp.promote_types(q.dtype, kbuf.dtype)
    q = q.astype(ct)
    col = lax.broadcasted_iota(jnp.int32, (H, C), 1)
    own_head = (col % K) == (lax.broadcasted_iota(jnp.int32, (H, C), 0) // G)
    tok = col // K                                  # token index in a tile
    limit = (n - 1) * bs + tail_ref[g, r]

    def tile(t, carry):
        acc, m, l = carry
        slot = t % 2

        @pl.when(t + 1 < ntiles)
        def _next():
            fetch(t + 1, 1 - slot)

        wait(t, slot)
        k = kbuf[slot].reshape(C, -1).astype(ct)
        v = vbuf[slot].reshape(C, -1).astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(own_head & (t * (pb * bs) + tok < limit), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m - shift)                  # 0 while m is -inf
        p = jnp.exp(s - shift)                      # masked scores -> 0
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return acc * alpha + pv, m_new, l

    acc, m, l = lax.fori_loop(
        0, ntiles, tile,
        (jnp.zeros(o_ref.shape[2:], jnp.float32),
         jnp.full((H, 1), NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32)))
    o_ref[0, 0] = acc
    m_ref[0, 0] = m.T
    l_ref[0, 0] = l.T


def paged_micro_attention_kernel(
    q: jax.Array,          # [R, H, D]  one query per request, every rank
    pools_k: jax.Array,    # [NR, NB, bs, K, D]
    pools_v: jax.Array,
    tables: jax.Array,     # [NR, R, MB] int32 (valid prefix, -1 padded)
    nblk: jax.Array,       # [NR, R] int32 valid slots per row
    tails: jax.Array,      # [NR, R] int32 valid tokens in the last slot
    *,
    scale: float,
    tile_blocks: int,
    interpret: bool,
):
    """Returns (o [NR, R, H, D], m [NR, R, 1, H], l [NR, R, 1, H]),
    float32; ``tile_blocks`` table slots per compute tile."""
    R, H, D = q.shape
    NR, NB, bs, K, _ = pools_k.shape
    G = H // K
    tile = (2, tile_blocks, bs, K, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(NR, R),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda g, r, *_: (r, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, D), lambda g, r, *_: (g, r, 0, 0)),
            pl.BlockSpec((1, 1, 1, H), lambda g, r, *_: (g, r, 0, 0)),
            pl.BlockSpec((1, 1, 1, H), lambda g, r, *_: (g, r, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM(tile, pools_k.dtype),
            pltpu.VMEM(tile, pools_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, K=K, G=G, pb=tile_blocks,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((NR, R, H, D), jnp.float32),
            jax.ShapeDtypeStruct((NR, R, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((NR, R, 1, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_micro_attention",
    )(tables, nblk, tails, q, pools_k, pools_v)
