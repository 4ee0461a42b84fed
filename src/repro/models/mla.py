"""Latent attention (MLA, DeepSeek-V2) and its paged serving steps.

Each layer projects a token to a compressed KV ``c_kv`` (``kv_lora_rank``
wide, RMS-normed) and one rotary key ``k_pe`` shared by every head; the
pool caches the row ``[c_kv | rope(k_pe)]`` (``cfg.latent_dim`` wide,
zero-filled to ``cfg.latent_pool_dim``) in ONE stacked pool
``[L, NB, bs, latent_pool_dim]`` per rank, where the dense families keep
a K and a V pool. Heads expand the row through ``wkv_b``: ``W_UK`` for
the no-rope keys and ``W_UV`` for the values.

Decode runs absorbed: ``q_nope`` is folded through ``W_UK`` into the
latent space, so every rank's partial (paper Eq. 2) is taken over the
rows themselves, ``(o_latent [H, kv_lora_rank], m, l)``, by the paged
latent kernel, which reads the stacked pool at a layer offset. The
partials merge by the unchanged log-sum-exp (Eq. 3); ``W_UV`` and the
output projection run once, after the merge. The new token's row enters
as a self partial and every layer's row is written into the owner pool
after the layer scan, so the pools are read-only inside it: no layer of
a pool is ever sliced out or copied back.

Prefill chunks run in plain ``jax.numpy``, absorbed as well: each chunk
attends to its paged prefix on every rank in tiles of ``PREFIX_TILE``
blocks, gathered from the stacked pool, and to itself under the causal
mask; its rows are written after the scan and exported for the
creditor-bound part of the prompt.

Rotary positions follow DeepSeek-V2: YaRN-scaled inverse frequencies
(the ramp between ``rope_beta_fast`` and ``rope_beta_slow`` rotations
over the original context), and the pairing that de-interleaves the
64 rope dims into halves before the half-split rotation. The softmax
scale is ``(qk_nope + qk_rope) ** -0.5 * mscale(mscale_all_dim) ** 2``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.online_softmax import combine, finalize
from repro.kernels.ops import (paged_latent_attention, resolve_backend,
                               write_latent_rows)
from repro.models.common import apply_ffn, apply_norm, dense_init
from repro.models.model import embed_tokens, unembed
from repro.models.moe import apply_moe
from repro.models.prefill import note_paged_trace, note_prefill_chunk_trace

PREFIX_TILE = 256        # table slots per prefix tile of a prefill chunk


# ===================================================================== #
# YaRN rotary positions
# ===================================================================== #
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies of the rope dims (float32 [qk_rope / 2]):
    plain rope, or YaRN's blend of extrapolated and ``rope_factor``-
    interpolated frequencies by the linear ramp between the dims that
    turn ``rope_beta_fast`` and ``rope_beta_slow`` times over the
    original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1.0:
        return extra.astype(np.float32)
    inter = extra / cfg.rope_factor
    orig = cfg.rope_original_max_pos
    low = max(math.floor(_correction_dim(cfg.rope_beta_fast, dim, base,
                                         orig)), 0)
    high = min(math.ceil(_correction_dim(cfg.rope_beta_slow, dim, base,
                                         orig)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1: extrapolate, 0: interpolate
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg: ModelConfig) -> float:
    """Score scale of latent attention (DeepSeek-V2 with YaRN)."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor > 1.0 and cfg.rope_mscale_all_dim:
        s *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return s


def apply_rope_pairs(x: jax.Array, positions: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    """Rotate x [..., T, h, qk_rope] at positions [..., T]: the rope dims
    are de-interleaved (pairs (2i, 2i+1) become halves), then rotated
    half against half; cos and sin carry YaRN's mscale ratio."""
    r = x.shape[-1]
    xf = x.astype(jnp.float32)
    xf = xf.reshape(x.shape[:-1] + (r // 2, 2))
    xf = jnp.swapaxes(xf, -1, -2).reshape(x.shape)
    ang = positions[..., None].astype(jnp.float32) * rope_inv_freq(cfg)
    ms = 1.0
    if cfg.rope_factor > 1.0:
        ms = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
              / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos = (jnp.cos(ang) * ms)[..., None, :]
    sin = (jnp.sin(ang) * ms)[..., None, :]
    x1, x2 = xf[..., :r // 2], xf[..., r // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ===================================================================== #
# Parameters and projections
# ===================================================================== #
def init_mla(key, cfg: ModelConfig):
    """Latent attention weights: the query (full rank, or ``wq_a`` ->
    norm -> ``wq_b`` with a query LoRA), ``wkv_a`` (to ``[c_kv | k_pe]``),
    the ``c_kv`` norm, ``wkv_b`` (``c_kv`` to every head's no-rope key
    and value) and the output projection."""
    d, H = cfg.d_model, cfg.num_heads
    n, rp, r, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                   cfg.kv_lora_rank, cfg.v_head_dim)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {"wkv_a": dense_init(ks[1], d, r + rp, dt),
         "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
         "wkv_b": dense_init(ks[2], r, H * (n + v), dt),
         "wo": dense_init(ks[3], H * v, d, dt)}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dt)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)}
        p["wq_b"] = dense_init(ks[4], cfg.q_lora_rank, H * (n + rp), dt)
    else:
        p["wq"] = dense_init(ks[0], d, H * (n + rp), dt)
    return p


def _pad_row(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    pad = cfg.latent_pool_dim - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def latent_project(p, x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    """x [B, T, d] at positions [B, T] -> the absorbed queries
    [B, T, H, latent_pool_dim] (``q_nope W_UK`` beside the rotated
    ``q_pe``) and the tokens' cache rows [B, T, latent_pool_dim]
    (``[norm(c_kv) | rope(k_pe)]``), zero-filled past ``latent_dim``."""
    B, T, _ = x.shape
    H, n, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = apply_norm(p["q_norm"], x @ p["wq_a"], cfg) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, T, H, -1)
    q_pe = apply_rope_pairs(q[..., n:], positions, cfg)
    kv = x @ p["wkv_a"]
    c = apply_norm(p["kv_norm"], kv[..., :r], cfg)
    k_pe = apply_rope_pairs(kv[..., None, r:], positions, cfg)[..., 0, :]
    w_uk = p["wkv_b"].reshape(r, H, -1)[..., :n]
    q_abs = jnp.einsum("bthn,rhn->bthr", q[..., :n], w_uk)
    return (_pad_row(jnp.concatenate([q_abs, q_pe], -1), cfg),
            _pad_row(jnp.concatenate([c, k_pe], -1), cfg))


def latent_out(p, o_lat: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Merged latent outputs [B, T, H, kv_lora_rank] -> [B, T, d]:
    every head's value through ``W_UV``, then the output projection."""
    B, T, H, r = o_lat.shape
    w_uv = p["wkv_b"].reshape(r, H, -1)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(w_uv.dtype), w_uv)
    return o.reshape(B, T, -1) @ p["wo"]


def _ffn(lp, h, cfg: ModelConfig, moe: bool):
    """(feed-forward output, held top-k assignments per row [B, T])."""
    if moe:
        return apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0,
                         with_held=True)
    return apply_ffn(lp["ffn"], h, cfg), jnp.zeros(h.shape[:2], jnp.int32)


def _scan_layers(params, cfg: ModelConfig, x, body):
    """Scan ``body(x, lp, layer, moe) -> (x, ys)`` over the leading dense
    layers, then the expert layers, with each layer's index; returns x
    and the ys stacked over all layers."""
    ys, lo = [], 0
    for name, moe in (("dense_layers", False), ("moe_layers", True)):
        if name not in params:
            continue
        stack = params[name]
        n = jax.tree.leaves(stack)[0].shape[0]
        x, y = jax.lax.scan(
            lambda x, xs, moe=moe: body(x, xs[0], xs[1], moe), x,
            (stack, lo + jnp.arange(n, dtype=jnp.int32)))
        ys.append(y)
        lo += n
    return x, jax.tree.map(lambda *a: jnp.concatenate(a, 0), *ys)


# ===================================================================== #
# Paged decode
# ===================================================================== #
@functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                   donate_argnames=("pool",))
def _decode_step_latent_jit(params, tokens, lens, pool, remote_pools,
                            tables, tails, write_block, write_off, *,
                            cfg, backend):
    note_paged_trace()
    r, scale = cfg.kv_lora_rank, softmax_scale(cfg)
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])
    # The owner's new row lands after the scan: its slot stays masked in
    # the pool and joins each layer as the self partial.
    tails = tails.at[0].add(-1)
    pools = (pool,) + tuple(remote_pools)

    def body(x, lp, layer, moe):
        h = apply_norm(lp["ln1"], x, cfg)
        q, row = latent_project(lp["attn"], h, lens[:, None], cfg)
        q, row = q[:, 0].astype(pool.dtype), row[:, 0].astype(pool.dtype)
        s = jnp.einsum("bhw,bw->bh", q, row,
                       preferred_element_type=jnp.float32) * scale
        part = (jnp.broadcast_to(row[:, None, :r].astype(jnp.float32),
                                 q.shape[:2] + (r,)),
                s, jnp.ones_like(s))
        for p, rank_pool in enumerate(pools):
            part = combine(part, paged_latent_attention(
                q, rank_pool, layer, tables[p], tails[p], rank=r,
                scale=scale, backend=backend))
        o = finalize(part[0], part[2])
        x = x + latent_out(lp["attn"], o[:, None], cfg)
        y, held = _ffn(lp, apply_norm(lp["ln2"], x, cfg), cfg, moe)
        return x + y, (row, held[:, 0])

    x, (rows, held) = _scan_layers(params, cfg, x, body)
    pool = write_latent_rows(pool, rows, write_block, write_off)
    return unembed(params, cfg, x[:, 0]), pool, jnp.sum(held, 0)


def decode_step_latent(params, cfg: ModelConfig, tokens, lens,
                       pool: jax.Array, tables, tails, write_block,
                       write_off, remote_pools: Sequence[jax.Array] = (),
                       *, backend: Optional[str] = None):
    """Fixed-shape paged DistAttention decode of the latent family.

    pool: the owner rank's [L, NB, bs, latent_pool_dim] pool, DONATED
    (continue with the returned array); remote_pools: creditor pools,
    read-only; tables/tails [P, B, MB] / [P, B] over (owner, *creditors)
    from ``build_local_tables``, the owner's counting the new token;
    write_block/write_off [B] its owner-pool slot (block NB: none).
    Returns (logits [B, V], new pool, held [B]: each row's top-k
    assignments that landed on held experts, summed over the layers).
    """
    assert cfg.is_latent, "the latent decode step serves family 'mla'"
    return _decode_step_latent_jit(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
        pool, tuple(remote_pools), jnp.asarray(tables, jnp.int32),
        jnp.asarray(tails, jnp.int32), jnp.asarray(write_block, jnp.int32),
        jnp.asarray(write_off, jnp.int32), cfg=cfg,
        backend=resolve_backend(backend))


# ===================================================================== #
# Chunked paged prefill
# ===================================================================== #
def _prefix_partial(q, pool, layer, table, tail, *, rank, scale):
    """One rank's partial of chunk queries q [C, H, W] over the prefix
    that ``table`` [MB] (MB a multiple of PREFIX_TILE) addresses in
    layer ``layer`` of the stacked pool, PREFIX_TILE blocks at a time;
    only the tiles that hold valid slots run."""
    L, NB, bs, W = pool.shape
    C, H, _ = q.shape
    flat = pool.reshape(L * NB, bs, W)
    nblk = jnp.sum(table >= 0)
    limit = (nblk - 1) * bs + tail
    tok = jnp.arange(PREFIX_TILE * bs)

    def tile(t, carry):
        o, m, l = carry
        blocks = jax.lax.dynamic_slice_in_dim(table, t * PREFIX_TILE,
                                              PREFIX_TILE)
        rows = flat[jnp.where(blocks >= 0, layer * NB + blocks, 0)]
        rows = rows.reshape(PREFIX_TILE * bs, W)
        s = jnp.einsum("chw,sw->chs", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(t * PREFIX_TILE * bs + tok < limit, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m - shift)
        p = jnp.exp(s - shift[..., None])
        pv = jnp.einsum("chs,sr->chr", p.astype(pool.dtype), rows[:, :rank],
                        preferred_element_type=jnp.float32)
        return o * alpha[..., None] + pv, m_new, l * alpha + p.sum(-1)

    n_tiles = (nblk + PREFIX_TILE - 1) // PREFIX_TILE
    return jax.lax.fori_loop(
        0, n_tiles, tile,
        (jnp.zeros((C, H, rank), jnp.float32),
         jnp.full((C, H), -jnp.inf, jnp.float32),
         jnp.zeros((C, H), jnp.float32)))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("pool",))
def _prefill_chunk_latent_jit(params, tokens, positions, valid, last_idx,
                              pool, remote_pools, tables, tails,
                              write_block, write_off, *, cfg):
    note_prefill_chunk_trace()
    r, scale = cfg.kv_lora_rank, softmax_scale(cfg)
    x = embed_tokens(params, cfg, tokens, None, positions)
    pools = (pool,) + tuple(remote_pools)
    qpos = positions[0]
    causal = (qpos[None, :] <= qpos[:, None]) & valid[0][None, :]

    def body(x, lp, layer, moe):
        h = apply_norm(lp["ln1"], x, cfg)
        q, rows = latent_project(lp["attn"], h, positions, cfg)
        q, rows = q[0].astype(pool.dtype), rows[0].astype(pool.dtype)
        s = jnp.einsum("chw,sw->chs", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(causal[:, None, :], s, -jnp.inf)
        m = jnp.max(s, -1)
        p = jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m)[..., None])
        part = (jnp.einsum("chs,sr->chr", p.astype(pool.dtype),
                           rows[:, :r],
                           preferred_element_type=jnp.float32),
                m, p.sum(-1))
        for p_i, rank_pool in enumerate(pools):
            part = combine(part, _prefix_partial(
                q, rank_pool, layer, tables[p_i], tails[p_i], rank=r,
                scale=scale))
        o = finalize(part[0], part[2])
        x = x + latent_out(lp["attn"], o[None], cfg)
        y, _ = _ffn(lp, apply_norm(lp["ln2"], x, cfg), cfg, moe)
        return x + y, rows

    x, rows = _scan_layers(params, cfg, x, body)
    pool = write_latent_rows(pool, rows, write_block, write_off)
    logits = unembed(params, cfg, jnp.take(x, last_idx, axis=1))
    return logits, pool, rows


def prefill_chunk_latent(params, cfg: ModelConfig, tokens, t0: int,
                         n_valid: int, pool: jax.Array, tables, tails,
                         write_block, write_off,
                         remote_pools: Sequence[jax.Array] = ()):
    """One fixed-shape streaming-prefill step of the latent family over
    prompt chunk [t0, t0 + C) (``prefill_chunk_paged``'s contract).

    tokens [C] (zero-padded past ``n_valid``); pool: the owner's stacked
    latent pool, DONATED; tables/tails [P, 1, MB] / [P, 1] addressing
    the written prefix [0, t0) on (owner, *creditors), re-padded here to
    a whole number of prefix tiles of the widest pool so that one
    program serves every prefix length; write_block/write_off [C]: each
    row's owner-pool slot (block NB: none). Returns (logits [1, V] at the
    last valid row, new pool, the chunk's rows [L, C, latent_pool_dim]:
    what the engine streams to creditor pools).
    """
    assert cfg.is_latent, "the latent prefill step serves family 'mla'"
    C = len(tokens)
    tables = np.asarray(tables, np.int32)[:, 0]
    nb = max(p.shape[1] for p in (pool,) + tuple(remote_pools))
    width = -(-max(nb, tables.shape[-1]) // PREFIX_TILE) * PREFIX_TILE
    tables = np.pad(tables, [(0, 0), (0, width - tables.shape[-1])],
                    constant_values=-1)
    positions = t0 + jnp.arange(C, dtype=jnp.int32)[None]
    valid = (jnp.arange(C, dtype=jnp.int32) < n_valid)[None]
    return _prefill_chunk_latent_jit(
        params, jnp.asarray(tokens, jnp.int32)[None], positions, valid,
        jnp.asarray(n_valid - 1, jnp.int32), pool, tuple(remote_pools),
        jnp.asarray(tables), jnp.asarray(np.asarray(tails, np.int32)[:, 0]),
        jnp.asarray(write_block, jnp.int32),
        jnp.asarray(write_off, jnp.int32), cfg=cfg)
