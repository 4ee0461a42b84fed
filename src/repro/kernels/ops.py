"""jit'd wrappers around the Pallas kernels: padding, dtype, auto-interpret.

Head dim is padded to a 128-lane multiple (zero-padding leaves q.k and
p.v unchanged, the softmax scale always uses the TRUE head dim), sequence
to the tile size. This module is the one place that resolves an unset
``interpret`` or attention ``backend`` from the process's default
backend (``_on_tpu``): the kernels themselves take ``interpret`` as a
required keyword, so the same code validates on CPU in interpret mode
and compiles natively on TPU, and never silently interprets on a chip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill import flash_prefill_kernel
from repro.kernels.micro_attn_decode import paged_micro_attention_kernel
from repro.kernels.micro_attn_prefill import \
    paged_prefill_micro_attention_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret: Optional[bool]) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def resolve_backend(backend: Optional[str]) -> str:
    """Paged-attention backend of a serving step: ``backend`` if given,
    else the Pallas kernels on a TPU and the jnp gather elsewhere."""
    if backend is not None:
        return backend
    return "pallas" if _on_tpu() else "jnp"


def _pad_last(x, mult):
    d = x.shape[-1]
    pad = (-d) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _pad_axis(x, axis, mult):
    d = x.shape[axis]
    pad = (-d) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bq", "bk",
                                             "interpret"))
def flash_prefill(q, k, v, *, scale=None, window=0, bq=128, bk=128,
                  interpret=None):
    """Causal flash attention. q [B,S,H,D], k/v [B,S,K,D] -> [B,S,H,D].

    The kernel runs head-major: operands are transposed to [B, H, S, D]
    (and back) so every tile is a Mosaic-aligned (bq, D) slab.
    """
    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5

    def head_major(x):
        return _pad_axis(_pad_last(x, 128), 1, bq).transpose(0, 2, 1, 3)

    out = flash_prefill_kernel(head_major(q), head_major(k), head_major(v),
                               seq=S, scale=scale, window=window, bq=bq,
                               bk=bk, interpret=_interpret(interpret))
    return out.transpose(0, 2, 1, 3)[:, :S, :, :D]


def paged_micro_attention_jnp(q, pool_k, pool_v, table, tail_len, *,
                              scale=None):
    """Pure-jnp paged MicroAttention partial — the gather fallback.

    Same contract as ``paged_micro_attention`` but built from a plain
    gather + ``micro_attention_decode`` so it fuses into surrounding jit
    code (e.g. the serving decode scan) on any backend, no Pallas needed.
    """
    from repro.core.distattn import gather_local_kv, local_mask_from_table
    from repro.core.online_softmax import micro_attention_decode
    bs = pool_k.shape[1]
    k, v = gather_local_kv(pool_k, pool_v, table)
    mask = local_mask_from_table(table, bs, tail_len)
    return micro_attention_decode(q, k, v, mask, scale=scale)


def paged_prefill_attention_jnp(q, pool_k, pool_v, table, tail_len, *,
                                scale=None):
    """Pure-jnp prefill-chunk paged partial — the gather fallback.

    All C chunk queries share the rank's ONE table, so the prefix rows
    are gathered once ([S, K, D]) and a shared-KV partial runs —
    transient stays O(prefix), never O(chunk x prefix). Fuses into
    surrounding jit code (the streaming-prefill scan) on any backend.
    """
    from repro.core.distattn import gather_local_kv, local_mask_from_table
    from repro.core.online_softmax import micro_attention_prefill
    bs = pool_k.shape[1]
    k, v = gather_local_kv(pool_k, pool_v, table[None])    # [1, S, K, D]
    valid = local_mask_from_table(table[None], bs, tail_len[None])
    # Every addressed token precedes every chunk query: q_pos=1 > kv_pos=0
    # keeps the causal test vacuously true for all (query, kv) pairs.
    q_pos = jnp.ones((1, q.shape[0]), jnp.int32)
    kv_pos = jnp.zeros_like(valid, jnp.int32)
    o, m, l = micro_attention_prefill(q[None], k, v, q_pos, kv_pos, valid,
                                      scale=scale)
    return o[0], m[0], l[0]


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "backend"))
def paged_prefill_attention(q, pool_k, pool_v, table, tail_len, *,
                            scale=None, interpret=None, backend=None):
    """Paged DistAttention MicroAttention partial (prefill chunk).

    q [C,H,D] — one chunk of query rows, all positioned AFTER the
    addressed prefix; pool_k/v [NB,bs,K,D]; table [MB] (-1 padded, seq
    order) shared by every query; tail_len [] valid tokens in the
    prefix's final block. ``backend``: "pallas" (kernel; interpret mode
    off-TPU) or "jnp" (pure gather fallback); None picks pallas on TPU
    and jnp elsewhere. Returns (o [C,H,D] f32 unnormalized, m [C,H] f32,
    l [C,H] f32) — LSE-mergeable with the chunk-internal causal partial.
    """
    C, H, D = q.shape
    NB, bs, K, _ = pool_k.shape
    if scale is None:
        scale = D ** -0.5
    table = table.astype(jnp.int32)
    tail_len = tail_len.astype(jnp.int32)
    if backend is None:
        backend = "pallas" if (_on_tpu() or interpret is not None) else "jnp"
    if backend == "jnp":
        return paged_prefill_attention_jnp(q, pool_k, pool_v, table,
                                           tail_len, scale=scale)
    G = H // K
    # kv-head-major query layout: each head group is a contiguous
    # [C*G, D] slab the kernel feeds to the MXU; rows padded to a
    # sublane multiple (padded rows compute garbage, sliced off below).
    qr = q.reshape(C, K, G, D).transpose(1, 0, 2, 3).reshape(K, C * G, D)
    qr = _pad_axis(qr, 1, 8)
    CGp = qr.shape[1]
    qp = _pad_last(qr.reshape(K * CGp, D), 128)
    kp = _pad_last(pool_k, 128)
    vp = _pad_last(pool_v, 128)
    nblk = jnp.sum(table >= 0)[None].astype(jnp.int32)
    o, m, l = paged_prefill_micro_attention_kernel(
        qp, kp, vp, table, nblk, tail_len[None], num_kv_heads=K,
        scale=scale, interpret=_interpret(interpret))
    o = o.reshape(K, CGp, -1)[:, :C * G, :D]
    m = m.reshape(K, CGp)[:, :C * G]
    l = l.reshape(K, CGp)[:, :C * G]
    o = o.reshape(K, C, G, D).transpose(1, 0, 2, 3).reshape(C, H, D)
    m = m.reshape(K, C, G).transpose(1, 0, 2).reshape(C, H)
    l = l.reshape(K, C, G).transpose(1, 0, 2).reshape(C, H)
    return o, m, l


# Bytes of one K or V tile buffer of the paged decode kernel (four are
# live: K and V, double-buffered): 16 blocks of mistral-nemo-12b's
# (16, 8, 128) bf16 or 8 of olmo-1b's (16, 16, 128). On a v5e these tiles
# ran the kernel at 83-85% (nemo) and 77% (olmo) of its HBM roofline;
# twice and four times as large ran no faster, and slower for olmo-1b's
# short rows, whose last tile is mostly padding slots that are computed.
_DECODE_TILE_BYTES = 512 * 1024


def _decode_tile_blocks(block_bytes: int, max_blocks: int) -> int:
    """Table slots per compute tile of the paged decode kernel: the
    largest power of two <= ``max_blocks`` whose blocks fit one tile
    buffer (at least one)."""
    pb = 1
    while 2 * pb <= max_blocks and 2 * pb * block_bytes <= _DECODE_TILE_BYTES:
        pb *= 2
    return pb


def _paged_decode_pallas(q, pools_k, pools_v, tables, tails, scale,
                         interpret):
    """The decode kernel over stacked rank pools; see
    ``paged_micro_attention_ranks``."""
    D = q.shape[-1]
    kp = _pad_last(pools_k, 128)
    vp = _pad_last(pools_v, 128)
    NR, NB, bs, K, Dp = kp.shape
    pb = _decode_tile_blocks(bs * K * Dp * kp.dtype.itemsize,
                             tables.shape[-1])
    nblk = jnp.sum(tables >= 0, axis=-1).astype(jnp.int32)
    o, m, l = paged_micro_attention_kernel(
        _pad_last(q, 128), kp, vp, tables, nblk, tails, scale=scale,
        tile_blocks=pb, interpret=_interpret(interpret))
    return o[..., :D], m[:, :, 0], l[:, :, 0]


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "backend"))
def paged_micro_attention(q, pool_k, pool_v, table, tail_len, *,
                          scale=None, interpret=None, backend=None):
    """Paged DistAttention MicroAttention partial (decode).

    q [R,H,D]; pool_k/v [NB,bs,K,D]; table [R,MB] (valid prefix in
    sequence order, -1 padded); tail_len [R] valid tokens in each
    request's LAST local slot.
    ``backend``: "pallas" (kernel; interpret mode off-TPU) or "jnp" (pure
    gather fallback); None picks pallas on TPU and jnp elsewhere.
    Returns (o [R,H,D] f32 unnormalized, m [R,H] f32, l [R,H] f32).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    table = table.astype(jnp.int32)
    tail_len = tail_len.astype(jnp.int32)
    if backend is None:
        backend = "pallas" if (_on_tpu() or interpret is not None) else "jnp"
    if backend == "jnp":
        return paged_micro_attention_jnp(q, pool_k, pool_v, table, tail_len,
                                         scale=scale)
    o, m, l = _paged_decode_pallas(q, pool_k[None], pool_v[None], table[None],
                                   tail_len[None], scale, interpret)
    return o[0], m[0], l[0]


def paged_micro_attention_ranks(q, pools_k, pools_v, tables, tails, *,
                                scale=None, backend=None):
    """Decode MicroAttention partials over a stacked set of rank pools.

    q [R,H,D] broadcast to every rank; pools_k/v [NR,NB,bs,K,D] one pool
    slab per rank; tables [NR,R,MB]; tails [NR,R]. One kernel call
    covers every rank. Returns stacked partials (o [NR,R,H,D],
    m [NR,R,H], l [NR,R,H]) — merge with ``merge_partials(axis=0)`` or
    compute per-shard inside shard_map and merge with
    ``merge_partials_collective``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    tables = tables.astype(jnp.int32)
    tails = tails.astype(jnp.int32)
    if resolve_backend(backend) == "jnp":
        return jax.vmap(
            lambda pk, pv, tb, tl: paged_micro_attention_jnp(
                q, pk, pv, tb, tl, scale=scale)
        )(pools_k, pools_v, tables, tails)
    return _paged_decode_pallas(q, pools_k, pools_v, tables, tails, scale,
                                None)


def paged_prefill_attention_ranks(q, pools_k, pools_v, tables, tails, *,
                                  scale=None, backend=None):
    """Prefill-chunk MicroAttention partials over stacked rank pools.

    q [C,H,D] chunk queries broadcast to every rank; pools_k/v
    [NR,NB,bs,K,D]; tables [NR,MB]; tails [NR]. Returns stacked partials
    (o [NR,C,H,D], m [NR,C,H], l [NR,C,H]).
    """
    return jax.vmap(
        lambda pk, pv, tb, tl: paged_prefill_attention(
            q, pk, pv, tb, tl, scale=scale, backend=backend)
    )(pools_k, pools_v, tables, tails)
