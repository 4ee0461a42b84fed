"""Mesh-level serving steps: paged DistAttention decode + pooled prefill.

Written in global view with sharding constraints so GSPMD materializes
the paper's communication pattern:

  * The KV pool is [L, NP, NB, bs, K, hd] with the NP axis sharded over
    ``pool_axes`` (("data",) in tp_head mode — kv heads over "model" —
    or ("data","model") when kv_heads < TP, where DistAttention's
    sequence sharding REPLACES head-TP; paper §7.4).
  * Every pool shard computes a MicroAttention partial over its local
    blocks (vmap over NP == per-shard local compute), and partials merge
    with ``merge_partials`` over the NP axis — lowering to the pmax/psum
    pattern of paper Eq. 3. Queries are broadcast; KV never moves.
  * Block-table metadata is host-provided and sharded like the pool, so
    placement changes are pure data — no recompilation (DESIGN.md §2).
  * Tail appends use the cluster pool's ONE dump convention (see the
    kvpool module docstring): per-shard write indices select either the
    request's tail block (on exactly one shard) or the OUT-OF-RANGE
    sentinel NB, and every scatter passes ``mode="drop"`` — no real
    dump slot is allocated, so the sharded and per-instance pools share
    the exact [NB, bs, K, hd] layout.

``decode_step_global``/``prefill_chunk_global`` at the bottom are the
serving cluster's entry into this file: the same paged steps the
engines run in-process, but over the cluster-wide ``GlobalKVPool``
tensor ``[ranks, L, NB, bs, K, hd]`` — vmapped over the rank axis on a
single device, shard_mapped with collective LSE-merges when a mesh is
attached.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.online_softmax import (combine, finalize,
                                       merge_partials,
                                       merge_partials_collective,
                                       micro_attention_decode,
                                       micro_attention_prefill)
from repro.kernels.ops import resolve_backend
from repro.kernels.ref import paged_micro_attention_ref
from repro.models.attention import make_causal_core, qkv_project
from repro.models.common import apply_ffn, apply_norm
from repro.models.model import embed_tokens, unembed
from repro.models.moe import apply_moe

wsc = jax.lax.with_sharding_constraint


@dataclasses.dataclass(frozen=True)
class ServeLayout:
    """Mesh-axis assignment for the serving step."""
    batch_axes: Tuple[str, ...]          # ("data",) or ("pod","data")
    pool_axes: Tuple[str, ...]           # + ("model",) in seq_model mode
    tp_axis: str = "model"

    @property
    def seq_model(self) -> bool:
        """True when the TP axis also shards the pool's sequence dim."""
        return self.tp_axis in self.pool_axes

    @property
    def kv_head_axis(self):
        """In tp_head mode the pool's kv-head dim shards over the TP
        axis; in seq_model mode the sequence (NP) dim already covers it."""
        return None if self.seq_model else self.tp_axis

    def pool_spec(self) -> P:
        """Spec for [NP, NB, bs, K, hd] (prepend None for the L dim)."""
        return P(self.pool_axes, None, None, self.kv_head_axis, None)


def _paged_partial(q, pool_k_l, pool_v_l, tables, nblk, tails, scale):
    """vmap over pool shards: per-shard MicroAttention partial.

    q [R,H,hd] (replicated); pool_*_l [NP,NB,bs,K,hd]; tables [NP,R,MB].
    Returns merged attention output [R,H,hd] (paper Eq. 2+3).
    """
    part = jax.vmap(
        lambda pk, pv, tb, nb, tl: paged_micro_attention_ref(
            q, pk, pv, tb, nb, tl, scale=scale)
    )(pool_k_l, pool_v_l, tables, nblk, tails)
    o, m, l = part                                # [NP, R, H, hd] etc.
    og, mg, lg = merge_partials(o, m, l, axis=0)  # lowers to Eq. 3 psums
    return finalize(og, lg)


def _write_kv(pool_l, new, wblk, woff):
    """Append one token's K (or V) into each request's tail block.

    pool_l [NP, NB, bs, K, hd]; new [R, K, hd]; wblk/woff [NP, R]
    (block index NB == out-of-range sentinel on shards that don't own
    the tail; mode="drop" skips those writes — the one tail-append
    scheme, see the kvpool docstring).
    """
    def one(pool_p, wb, wo):
        return pool_p.at[wb, wo].set(new, mode="drop")
    return jax.vmap(one)(pool_l, wblk, woff)


def serve_decode_step(params, cfg: ModelConfig, layout: ServeLayout,
                      pool_k, pool_v, tables, nblk, tails, wblk, woff,
                      tokens, lens, *, capacity_factor: float = 1.25,
                      return_logits: bool = False,
                      layer_constraints=None):
    """One decode iteration for R requests over the whole mesh.

    pool_k/v: [L, NP, NB, bs, K, hd]; tables [NP, R, MB]; nblk/tails
    [NP, R]; wblk/woff [NP, R]; tokens/lens [R].
    Returns (next_tokens [R], new_pool_k, new_pool_v).
    """
    R = tokens.shape[0]
    scale = cfg.head_dim ** -0.5

    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])
    x = wsc(x, P(layout.batch_axes, None, None))

    def attn_layer(lp, x, pk_l, pv_l):
        """One layer's attention: write new KV, paged partial, merge."""
        h = apply_norm(lp["ln1"], x, cfg)
        q, k, v = qkv_project(lp["attn"], h, lens[:, None], cfg)
        pk_l = _write_kv(pk_l, k[:, 0], wblk, woff)
        pv_l = _write_kv(pv_l, v[:, 0], wblk, woff)
        out = _paged_partial(q[:, 0], pk_l, pv_l, tables, nblk, tails,
                             scale)
        out = out.reshape(R, 1, -1).astype(x.dtype) @ lp["attn"]["wo"]
        x = x + wsc(out, P(layout.batch_axes, None, None))
        return x, pk_l, pv_l

    def ffn_part(lp, x, moe):
        """One layer's FFN/MoE half."""
        h = apply_norm(lp["ln2"], x, cfg)
        if moe:
            x = x + apply_moe(lp["moe"], h, cfg, capacity_factor)
        else:
            x = x + apply_ffn(lp["ffn"], h, cfg)
        return wsc(x, P(layout.batch_axes, None, None))

    lc = layer_constraints or {}

    def make_body(moe, name):
        """Scan body factory for the ``moe``/dense layer stack."""
        def body(x, xs):
            """Scanned per-layer step (attention + FFN)."""
            lp, pk_l, pv_l = xs
            if name in lc:
                lp = lc[name](lp)
            x, pk_l, pv_l = attn_layer(lp, x, pk_l, pv_l)
            x = ffn_part(lp, x, moe)
            return x, (pk_l, pv_l)
        return body

    if cfg.family == "dense":
        x, (pk, pv) = jax.lax.scan(make_body(False, "layers"), x,
                                   (params["layers"], pool_k, pool_v))
    elif cfg.family == "moe":
        nd = cfg.first_k_dense
        if nd:
            x, (pkd, pvd) = jax.lax.scan(
                make_body(False, "dense_layers"), x,
                (params["dense_layers"], pool_k[:nd], pool_v[:nd]))
        x, (pkm, pvm) = jax.lax.scan(
            make_body(True, "moe_layers"), x,
            (params["moe_layers"], pool_k[nd:], pool_v[nd:]))
        pk = jnp.concatenate([pkd, pkm], 0) if nd else pkm
        pv = jnp.concatenate([pvd, pvm], 0) if nd else pvm
    else:
        raise ValueError("sharded decode pools KV only for attention "
                         "archs; hybrid/ssm use serve_decode_step_state")

    logits = unembed(params, cfg, x[:, 0])
    if return_logits:
        return logits, pk, pv
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, pk, pv


# --------------------------------------------------------------------- #
# Optimized decode (§Perf-1): read-only pool scan + deferred writes
# --------------------------------------------------------------------- #
def _paged_partial_fullpool(q, pool_k_l, pool_v_l, tables, nblk, tails,
                            scale):
    """In-place MicroAttention over the WHOLE local pool with an
    owner-validity mask — zero gathers, zero pool copies. Optimal when
    the pool mostly belongs to few requests (long-context decode, R~1):
    reads each pool byte exactly once; invalid slots are masked.

    (A per-slot gather formulation was tried first and REFUTED: GSPMD
    lowers the sharded-dim gather to a masked all-reduce, 268 MB/iter —
    see EXPERIMENTS.md §Perf-1 iteration 1.)
    """
    from repro.core.online_softmax import micro_attention_decode
    NP, NB, bs, K, hd = pool_k_l.shape
    R = q.shape[0]
    # Which pool slot is valid for which request, from the tables.
    oh = jax.nn.one_hot(jnp.clip(tables, 0, NB - 1), NB,
                        dtype=jnp.bool_)                # [NP,R,MB,NB]
    oh = oh & (tables >= 0)[..., None]
    block_valid = oh.any(axis=2)                        # [NP, R, NB]
    tail_blk = jnp.take_along_axis(
        tables, jnp.maximum(nblk - 1, 0)[..., None], axis=2)[..., 0]
    is_tail = (jnp.arange(NB)[None, None, :] == tail_blk[..., None]) \
        & block_valid
    limit = jnp.where(is_tail, tails[..., None], bs)    # [NP, R, NB]
    tok_ok = jnp.arange(bs)[None, None, None, :] < limit[..., None]
    mask = (block_valid[..., None] & tok_ok).reshape(NP, R, NB * bs)

    kf = pool_k_l.reshape(NP, NB * bs, K, hd)
    vf = pool_v_l.reshape(NP, NB * bs, K, hd)
    # Pool KV is shared across requests (each request masks its slots):
    # broadcast the request dim lazily (fullpool is only used for R~1).
    part = jax.vmap(lambda kb, vb, va: micro_attention_decode(
        q, jnp.broadcast_to(kb[None], (R,) + kb.shape),
        jnp.broadcast_to(vb[None], (R,) + vb.shape), va,
        scale=scale))(kf, vf, mask)
    return part                                          # [NP, ...]


def serve_decode_step_opt(params, cfg: ModelConfig, layout: ServeLayout,
                          pool_k, pool_v, tables, nblk, tails, wblk, woff,
                          tokens, lens, *, capacity_factor: float = 1.25,
                          return_logits: bool = False,
                          layer_constraints=None):
    """Beyond-paper-optimized decode (§Perf-1). Same math, new schedule:

    1. The pool rides through the layer scan READ-ONLY (xs, not carry),
       killing the per-layer double-buffer copy of the whole pool.
    2. The new token's KV joins attention as an explicit *self partial*
       merged once (Eq. 3 is associative), so no in-scan pool write.
    3. All L layers' new KV is written AFTER the scan in one batched
       scatter (k_new collected as scan ys).
    4. Per-shard attention is a block-scan (``_paged_partial_blockscan``)
       reading each pool block exactly once.

    NOTE: ``tails``/``nblk`` here describe the pool WITHOUT the new
    token (the engine increments them after the step).
    """
    from repro.core.online_softmax import (combine, finalize,
                                           micro_attention_decode)
    R = tokens.shape[0]
    scale = cfg.head_dim ** -0.5
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])
    x = wsc(x, P(layout.batch_axes, None, None))
    lc = layer_constraints or {}

    def attn_layer(lp, x):
        """QKV projection only; the paged partial runs in the body."""
        h = apply_norm(lp["ln1"], x, cfg)
        q, k, v = qkv_project(lp["attn"], h, lens[:, None], cfg)
        return q, k, v, x

    def make_body(moe, name):
        """Scan body factory for the ``moe``/dense layer stack."""
        def body(x, xs):
            """Scanned per-layer step (attention + FFN)."""
            lp, pk_l, pv_l = xs
            if name in lc:
                lp = lc[name](lp)
            q, k, v, x = attn_layer(lp, x)
            NB_l, bs = pk_l.shape[1], pk_l.shape[2]
            if R * NB_l * bs <= 2 * NB_l * bs * tables.shape[0] \
                    and not os.environ.get('REPRO_FORCE_GATHER'):
                # Few requests own most of the pool: mask, don't gather.
                part = _paged_partial_fullpool(q[:, 0], pk_l, pv_l,
                                               tables, nblk, tails, scale)
                pooled = merge_partials(*part, axis=0)
            else:
                o_, m_, l_ = jax.vmap(
                    lambda pk, pv, tb, nb, tl: paged_micro_attention_ref(
                        q[:, 0], pk, pv, tb, nb, tl, scale=scale)
                )(pk_l, pv_l, tables, nblk, tails)
                pooled = merge_partials(o_, m_, l_, axis=0)
            self_part = micro_attention_decode(
                q[:, 0], k, v, jnp.ones((R, 1), bool), scale=scale)
            o, m, l = combine(pooled, self_part)
            out = finalize(o, l)
            out = out.reshape(R, 1, -1).astype(x.dtype) @ lp["attn"]["wo"]
            x = x + wsc(out, P(layout.batch_axes, None, None))
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            x = wsc(x, P(layout.batch_axes, None, None))
            return x, (k[:, 0], v[:, 0])
        return body

    if cfg.family == "dense":
        x, (ks, vs) = jax.lax.scan(make_body(False, "layers"), x,
                                   (params["layers"], pool_k, pool_v))
    elif cfg.family == "moe":
        nd = cfg.first_k_dense
        if nd:
            x, (kd, vd) = jax.lax.scan(
                make_body(False, "dense_layers"), x,
                (params["dense_layers"], pool_k[:nd], pool_v[:nd]))
        x, (km, vm) = jax.lax.scan(
            make_body(True, "moe_layers"), x,
            (params["moe_layers"], pool_k[nd:], pool_v[nd:]))
        ks = jnp.concatenate([kd, km], 0) if nd else km
        vs = jnp.concatenate([vd, vm], 0) if nd else vm
    else:
        raise ValueError("pooled decode is for attention archs")

    # Deferred batched write: one scatter for all layers.
    pk = jax.vmap(lambda p, n: _write_kv(p, n, wblk, woff))(pool_k, ks)
    pv = jax.vmap(lambda p, n: _write_kv(p, n, wblk, woff))(pool_v, vs)

    logits = unembed(params, cfg, x[:, 0])
    if return_logits:
        return logits, pk, pv
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, pk, pv


# --------------------------------------------------------------------- #
# Prefill: full-sequence forward + analytic round-robin pool writes
# --------------------------------------------------------------------- #
def prefill_layout(B: int, S: int, bs: int, NP: int,
                   n_data: Optional[int] = None):
    """Block placement at prefill time.

    Paper-faithful (and communication-free) layout when the batch divides
    the data axis: request b's blocks live on ITS OWN data rank — spread
    over the model sub-axis in seq_model mode — so the KV scatter is
    entirely local (the round-robin-over-all-shards layout was measured
    to all-gather the full [B*S,K,hd] KV per layer: §Perf-2 it.3).

    Returns (wblk [NP,B,S], woff [B,S], NB_loc). Non-local tokens get
    wblk == NB_loc — the OUT-OF-RANGE sentinel (the pool has exactly
    NB_loc blocks); writes use ``mode="drop"``, never a real dump slot.
    """
    nblocks = -(-S // bs)
    pos = jnp.arange(S, dtype=jnp.int32)
    blk = pos // bs                                   # [S]
    woff = jnp.broadcast_to(pos % bs, (B, S))
    p_idx = jnp.arange(NP, dtype=jnp.int32)[:, None, None]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

    if n_data and B % n_data == 0:
        n_sub = NP // n_data                          # model sub-shards
        per_data = B // n_data
        d_of_b = b_idx // per_data                    # [B,1] data rank
        sub = blk % n_sub                             # [S]
        shard_of = d_of_b * n_sub + sub[None]         # [B, S]
        per_req = -(-nblocks // n_sub)
        NB_loc = per_data * per_req
        local = (b_idx % per_data) * per_req + (blk // n_sub)[None]
        wblk = jnp.where(shard_of[None] == p_idx, local[None], NB_loc)
        return wblk, woff, NB_loc

    # Fallback: round-robin over all shards (correct, not comm-free).
    per_req = -(-nblocks // NP)
    NB_loc = B * per_req
    shard = blk % NP
    wblk_owner = b_idx * per_req + (blk // NP)[None]
    wblk = jnp.where(shard[None, None, :] == p_idx, wblk_owner[None],
                     NB_loc)
    return wblk, woff, NB_loc


def serve_prefill_step(params, cfg: ModelConfig, layout: ServeLayout,
                       tokens, *, block_size: int, NP: int,
                       n_data: Optional[int] = None,
                       embeds=None, capacity_factor: float = 1.25,
                       attn_chunk: int = 1024, layer_constraints=None,
                       seq_parallel: bool = False):
    """Prefill B requests of length S; write KV into a fresh pool.

    Returns (first_tokens [B], pool_k, pool_v [L, NP, NB, bs, K, hd]).
    """
    B, S = (tokens.shape if embeds is None else embeds.shape[:2])
    bs = block_size
    wblk, woff, NB_loc = prefill_layout(B, S, bs, NP, n_data=n_data)
    wblk = wsc(wblk, P(layout.pool_axes, None, None))
    positions = jnp.arange(S, dtype=jnp.int32)[None].repeat(B, 0)
    x = embed_tokens(params, cfg, tokens, embeds, positions)
    # Megatron-SP (beyond-paper, seq_parallel=True): keep the residual
    # stream SEQUENCE-sharded over the TP axis between blocks, so the
    # row-parallel all-reduces decompose into reduce-scatter + all-gather
    # (half the bytes) and norms compute 1/tp of the work.
    seq_ax = layout.tp_axis if (seq_parallel and S % 16 == 0) else None
    xspec = P(layout.batch_axes, seq_ax, None)
    x = wsc(x, xspec)
    # Pin the online-softmax carry to heads-over-TP so the chunk scan
    # never reshards it (§Perf-2: 2 all-reduces/chunk/layer otherwise).
    h_ax = layout.tp_axis if cfg.num_heads % 16 == 0 else None
    ba = layout.batch_axes

    def acc_pin(acc):
        """Sharding-pin the online-softmax carry (o, m, l)."""
        o, m, l = acc
        return (wsc(o, P(ba, None, h_ax, None)),
                wsc(m, P(ba, None, h_ax)), wsc(l, P(ba, None, h_ax)))

    core = make_causal_core(cfg, backend="xla", chunk=attn_chunk,
                            acc_constraint=acc_pin)
    K, hd = cfg.num_kv_heads, cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)

    nblocks = S // bs if S % bs == 0 else 0
    n_sub = NP // n_data if n_data else 0
    aligned = (n_data and B % n_data == 0 and S % bs == 0
               and n_sub and nblocks % n_sub == 0)

    def write_pool(k):                               # [B, S, K, hd]
        """Lay a layer's fresh KV into the global pool layout."""
        if aligned:
            # With the data-local layout, the pool IS a reshape of k:
            # pool[d*n_sub+sub, (b%pd)*pr + i] = k[b, (i*n_sub+sub)*bs:..]
            # — zero communication (k is replicated/sharded compatibly),
            # vs the scatter formulation that all-gathered the full
            # [B*S,K,hd] KV per layer (§Perf-2 iteration 3).
            pd, pr = B // n_data, nblocks // n_sub
            k5 = k.reshape(n_data, pd, pr, n_sub, bs, K, hd)
            k6 = jnp.moveaxis(k5, 3, 1)
            # Pin the pre-merge layout (dim0 -> data axes, dim1 -> model
            # sub-shard) so the merge-reshape below is a LOCAL slice, not
            # an all-gather + re-slice.
            if layout.seq_model:
                k6 = wsc(k6, P(layout.pool_axes[:-1], layout.tp_axis))
            else:
                k6 = wsc(k6, P(layout.pool_axes, None))
            pool = k6.reshape(NP, pd * pr, bs, K, hd)
            return wsc(pool, layout.pool_spec())
        pool = jnp.zeros((NP, NB_loc, bs, K, hd), dtype)
        pool = wsc(pool, layout.pool_spec())

        def one(pool_p, wb_p):
            """Per-rank scatter of every token into the local slice."""
            # Scatter all B*S tokens; non-local indices (NB_loc) drop.
            flat_b = wb_p.reshape(-1)
            flat_o = woff.reshape(-1)
            return pool_p.at[flat_b, flat_o].set(
                k.reshape(B * S, K, hd), mode="drop")
        return jax.vmap(one)(pool, wblk)

    def attn_layer(lp, x):
        """One prefill layer's attention over the full chunk."""
        h = apply_norm(lp["ln1"], x, cfg)
        q, k, v = qkv_project(lp["attn"], h, positions, cfg)
        out = core(q, k, v)
        out = out.reshape(B, S, -1).astype(x.dtype) @ lp["attn"]["wo"]
        x = x + wsc(out, xspec)
        return x, (write_pool(k), write_pool(v))

    lc = layer_constraints or {}

    def make_body(moe, name):
        """Scan body factory for the ``moe``/dense layer stack."""
        def body(x, lp):
            """Scanned per-layer prefill step."""
            if name in lc:
                lp = lc[name](lp)
            x, kv = attn_layer(lp, x)
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return wsc(x, xspec), kv
        return body

    if cfg.family == "dense":
        x, (pk, pv) = jax.lax.scan(make_body(False, "layers"), x,
                                   params["layers"])
    elif cfg.family == "moe":
        nd = cfg.first_k_dense
        if nd:
            x, (pkd, pvd) = jax.lax.scan(make_body(False, "dense_layers"),
                                         x, params["dense_layers"])
        x, (pkm, pvm) = jax.lax.scan(make_body(True, "moe_layers"), x,
                                     params["moe_layers"])
        pk = jnp.concatenate([pkd, pkm], 0) if nd else pkm
        pv = jnp.concatenate([pvd, pvm], 0) if nd else pvm
    else:
        raise ValueError("pooled prefill is for attention archs")

    logits = unembed(params, cfg, x[:, -1])
    return jnp.argmax(logits, -1).astype(jnp.int32), pk, pv


# --------------------------------------------------------------------- #
# Prefill for hybrid / ssm archs: forward + recurrent states (+ window)
# --------------------------------------------------------------------- #
def serve_prefill_step_state(params, cfg: ModelConfig, layout: ServeLayout,
                             tokens, *, max_len: int, embeds=None):
    """Returns (first_tokens [B], DecodeState) — the O(1)/windowed state
    these families decode from (no cluster KV pool involved)."""
    from repro.models.prefill import prefill
    logits, state = prefill(params, cfg, tokens, embeds, max_len=max_len)
    return jnp.argmax(logits, -1).astype(jnp.int32), state


# --------------------------------------------------------------------- #
# Stateful decode for hybrid / ssm archs (no KV pool to shard)
# --------------------------------------------------------------------- #
def serve_decode_step_state(params, cfg: ModelConfig, layout: ServeLayout,
                            state, tokens):
    """Hybrid/SSM decode: O(1)-state recurrence, batch over data axis.

    DistAttention is inapplicable (DESIGN.md §Arch-applicability); the
    local-attention window cache for hybrid archs rides in ``state``.
    """
    from repro.models.model import decode_step
    logits, new_state = decode_step(params, cfg, state, tokens)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    return nxt, new_state


# --------------------------------------------------------------------- #
# Global-pool steps: one [ranks, L, NB, bs, K, hd] tensor for the whole
# cluster (``serving.globalpool.GlobalKVPool``). Same paged math as the
# in-process engine steps (models/prefill.py), but every rank's pool is
# a slice of ONE array: vmapped over the rank axis on a single device,
# shard_mapped with collective LSE-merges (paper Eq. 3) under a mesh.
# --------------------------------------------------------------------- #
def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# Incremented once per trace of a global-pool jit; serving tests assert
# compiles stay bounded by (table bucket, rank count), never context.
_GLOBAL_TRACE_COUNT = 0


def global_trace_count() -> int:
    """Times a global-pool step retraced (tests bound this)."""
    return _GLOBAL_TRACE_COUNT


def _shard_rank_base(mesh, pool_axes, r_loc):
    """First global rank owned by the calling shard (inside shard_map)."""
    idx = jnp.int32(0)
    for ax in pool_axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx * r_loc


def _global_write_decode(g, rows, wblk, woff, *, rank, mesh, pool_axes):
    """Deferred decode tail-append: all L layers' new token in ONE
    scatter. rows [L, B, K, hd]; wblk/woff [B] (sentinel NB drops)."""
    val = jnp.swapaxes(rows, 0, 1).astype(g.dtype)        # [B, L, K, hd]
    if mesh is None:
        return g.at[rank, :, wblk, woff].set(val, mode="drop")

    def shard(gs, vs):
        lr = rank - _shard_rank_base(mesh, pool_axes, gs.shape[0])
        # lr lands outside [0, R_loc) on every shard but the owner's.
        # A NEGATIVE index would WRAP (JAX indexing), so remap it to
        # R_loc — genuinely out of bounds — and let mode="drop" skip it.
        lr = jnp.where((lr >= 0) & (lr < gs.shape[0]), lr, gs.shape[0])
        return gs.at[lr, :, wblk, woff].set(vs, mode="drop")

    return _shard_map(shard, mesh, in_specs=(P(pool_axes), P()),
                      out_specs=P(pool_axes))(g, val)


def _global_write_prefill(g, rows, wrank, wblk, woff, *, mesh, pool_axes):
    """Deferred prefill-chunk append: row c of the chunk lands in rank
    wrank[c] (any rank — owner OR creditor: the striped ``PrefixSink``
    write is now just more rows of this one scatter). rows [L, C, K, hd];
    wrank/wblk/woff [C] (block sentinel NB drops padding rows)."""
    val = jnp.swapaxes(rows, 0, 1).astype(g.dtype)        # [C, L, K, hd]
    if mesh is None:
        return g.at[wrank, :, wblk, woff].set(val, mode="drop")

    def shard(gs, vs):
        lr = wrank - _shard_rank_base(mesh, pool_axes, gs.shape[0])
        # Remap foreign ranks (negative lr would wrap, see above).
        lr = jnp.where((lr >= 0) & (lr < gs.shape[0]), lr, gs.shape[0])
        return gs.at[lr, :, wblk, woff].set(vs, mode="drop")

    return _shard_map(shard, mesh, in_specs=(P(pool_axes), P()),
                      out_specs=P(pool_axes))(g, val)


def _global_pooled_decode(q1, gk_l, gv_l, tables, tails, scale, *,
                          mesh, pool_axes, backend):
    """Merged pooled partial for one layer. q1 [B,H,hd] broadcast;
    gk_l/gv_l [NR,NB,bs,K,hd]; tables [NR,B,MB]; tails [NR,B]."""
    from repro.kernels.ops import paged_micro_attention_ranks
    if mesh is None:
        o, m, l = paged_micro_attention_ranks(q1, gk_l, gv_l, tables,
                                              tails, scale=scale,
                                              backend=backend)
        return merge_partials(o, m, l, axis=0)

    def shard(qs, pk, pv, tb, tl):
        o, m, l = paged_micro_attention_ranks(qs, pk, pv, tb, tl,
                                              scale=scale, backend=backend)
        o, m, l = merge_partials(o, m, l, axis=0)     # local ranks
        return merge_partials_collective(o, m, l, pool_axes)

    return _shard_map(shard, mesh,
                      in_specs=(P(), P(pool_axes), P(pool_axes),
                                P(pool_axes), P(pool_axes)),
                      out_specs=(P(), P(), P()))(q1, gk_l, gv_l,
                                                 tables, tails)


def _global_pooled_prefill(qc, gk_l, gv_l, tables, tails, scale, *,
                           mesh, pool_axes, backend):
    """Merged prefix partial for one prefill chunk. qc [C,H,hd];
    tables [NR,MB]; tails [NR]."""
    from repro.kernels.ops import paged_prefill_attention_ranks
    if mesh is None:
        o, m, l = paged_prefill_attention_ranks(qc, gk_l, gv_l, tables,
                                                tails, scale=scale,
                                                backend=backend)
        return merge_partials(o, m, l, axis=0)

    def shard(qs, pk, pv, tb, tl):
        o, m, l = paged_prefill_attention_ranks(qs, pk, pv, tb, tl,
                                                scale=scale,
                                                backend=backend)
        o, m, l = merge_partials(o, m, l, axis=0)
        return merge_partials_collective(o, m, l, pool_axes)

    return _shard_map(shard, mesh,
                      in_specs=(P(), P(pool_axes), P(pool_axes),
                                P(pool_axes), P(pool_axes)),
                      out_specs=(P(), P(), P()))(qc, gk_l, gv_l,
                                                 tables, tails)


def _scan_layers_global(params, cfg, x, make_body):
    """Layer scan with (lp, layer_index) xs — the global pool stays a
    closed-over READ-ONLY array (no per-layer pool carry copies)."""
    L = cfg.num_layers
    if cfg.family == "dense":
        return jax.lax.scan(make_body(False), x,
                            (params["layers"],
                             jnp.arange(L, dtype=jnp.int32)))
    nd = cfg.first_k_dense
    ys_d = None
    if nd:
        x, ys_d = jax.lax.scan(make_body(False), x,
                               (params["dense_layers"],
                                jnp.arange(nd, dtype=jnp.int32)))
    x, ys_m = jax.lax.scan(make_body(True), x,
                           (params["moe_layers"],
                            jnp.arange(nd, L, dtype=jnp.int32)))
    if nd:
        ys_m = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                            ys_d, ys_m)
    return x, ys_m


@functools.partial(jax.jit,
                   static_argnames=("cfg", "backend", "mesh", "pool_axes",
                                    "rank"),
                   donate_argnames=("gk", "gv"))
def _decode_step_global_jit(params, tokens, lens, gk, gv, tables, tails,
                            wblk, woff, *, cfg, backend, mesh, pool_axes,
                            rank):
    global _GLOBAL_TRACE_COUNT
    _GLOBAL_TRACE_COUNT += 1
    B = tokens.shape[0]
    scale = cfg.head_dim ** -0.5
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])

    def make_body(moe):
        def body(x, xs):
            lp, li = xs
            h = apply_norm(lp["ln1"], x, cfg)
            q, k, v = qkv_project(lp["attn"], h, lens[:, None], cfg)
            gk_l = jax.lax.dynamic_index_in_dim(gk, li, axis=1,
                                                keepdims=False)
            gv_l = jax.lax.dynamic_index_in_dim(gv, li, axis=1,
                                                keepdims=False)
            pooled = _global_pooled_decode(q[:, 0], gk_l, gv_l, tables,
                                           tails, scale, mesh=mesh,
                                           pool_axes=pool_axes,
                                           backend=backend)
            # §Perf-1 schedule: the pool rides read-only; the new token
            # joins as an explicit self partial (tables/tails passed in
            # EXCLUDE it) and its KV is written after the scan.
            self_part = micro_attention_decode(
                q[:, 0], k, v, jnp.ones((B, 1), bool), scale=scale)
            o, m, l = combine(pooled, self_part)
            out = finalize(o, l)
            out = out.reshape(B, 1, -1).astype(x.dtype) @ lp["attn"]["wo"]
            x = x + out
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return x, (k[:, 0], v[:, 0])
        return body

    x, (ks, vs) = _scan_layers_global(params, cfg, x, make_body)
    gk = _global_write_decode(gk, ks, wblk, woff, rank=rank, mesh=mesh,
                              pool_axes=pool_axes)
    gv = _global_write_decode(gv, vs, wblk, woff, rank=rank, mesh=mesh,
                              pool_axes=pool_axes)
    logits = unembed(params, cfg, x[:, 0])
    return logits, gk, gv


def decode_step_global(params, cfg: ModelConfig, tokens, lens, gk, gv,
                       tables, tails, wblk, woff, *, rank: int, mesh=None,
                       pool_axes: Tuple[str, ...] = ("data",),
                       backend: Optional[str] = None):
    """Paged DistAttention decode over the GLOBAL pool tensor.

    tokens/lens: [B]; gk/gv: [NR, L, NB, bs, K, hd] — the whole
    cluster's KV, DONATED (continue with the returned arrays);
    tables/tails: [NR, B, MB] / [NR, B] from ``build_local_tables`` over
    ``GlobalKVPool.ranks``, POST-EDITED so the pending token's slot is
    excluded (it enters as a self partial); wblk/woff: [B] tail target
    in rank ``rank``'s slice (sentinel NB drops); ``rank``: the calling
    engine's rank (static — there are only NR of them). With ``mesh``,
    the rank axis shards over ``pool_axes`` and each shard computes its
    partial under shard_map; partials LSE-merge with pmax/psum (Eq. 3).
    Queries broadcast; KV never moves. Returns (logits, gk, gv).
    """
    assert cfg.family in ("dense", "moe"), "only attention archs pool KV"
    backend = resolve_backend(backend)
    return _decode_step_global_jit(
        params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(lens, jnp.int32), gk, gv,
        jnp.asarray(tables, jnp.int32), jnp.asarray(tails, jnp.int32),
        jnp.asarray(wblk, jnp.int32), jnp.asarray(woff, jnp.int32),
        cfg=cfg, backend=backend, mesh=mesh,
        pool_axes=tuple(pool_axes), rank=rank)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "backend", "mesh", "pool_axes"),
                   donate_argnames=("gk", "gv"))
def _prefill_chunk_global_jit(params, tokens, positions, valid, last_idx,
                              gk, gv, tables, tails, wrank, wblk, woff,
                              *, cfg, backend, mesh, pool_axes):
    global _GLOBAL_TRACE_COUNT
    _GLOBAL_TRACE_COUNT += 1
    scale = cfg.head_dim ** -0.5
    x = embed_tokens(params, cfg, tokens, None, positions)
    B, C = x.shape[:2]

    def make_body(moe):
        def body(x, xs):
            lp, li = xs
            h = apply_norm(lp["ln1"], x, cfg)
            q, k, v = qkv_project(lp["attn"], h, positions, cfg)
            gk_l = jax.lax.dynamic_index_in_dim(gk, li, axis=1,
                                                keepdims=False)
            gv_l = jax.lax.dynamic_index_in_dim(gv, li, axis=1,
                                                keepdims=False)
            # Prefix partial over the written tokens [0, t0) on EVERY
            # rank (tables mask this chunk's rows out), + chunk-causal.
            part = _global_pooled_prefill(q[0], gk_l, gv_l, tables,
                                          tails, scale, mesh=mesh,
                                          pool_axes=pool_axes,
                                          backend=backend)
            o_c, m_c, l_c = micro_attention_prefill(q, k, v, positions,
                                                    positions, valid)
            part = combine(part, (o_c[0], m_c[0], l_c[0]))
            out = finalize(part[0], part[2])
            out = out.reshape(B, C, -1).astype(x.dtype) @ lp["attn"]["wo"]
            x = x + out
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return x, (k[0], v[0])
        return body

    x, (ks, vs) = _scan_layers_global(params, cfg, x, make_body)
    gk = _global_write_prefill(gk, ks, wrank, wblk, woff, mesh=mesh,
                               pool_axes=pool_axes)
    gv = _global_write_prefill(gv, vs, wrank, wblk, woff, mesh=mesh,
                               pool_axes=pool_axes)
    logits = unembed(params, cfg, jnp.take(x, last_idx, axis=1))
    return logits, gk, gv, ks, vs


def prefill_chunk_global(params, cfg: ModelConfig, tokens, t0: int,
                         n_valid: int, gk, gv, tables, tails, wrank,
                         wblk, woff, *, mesh=None,
                         pool_axes: Tuple[str, ...] = ("data",),
                         backend: Optional[str] = None):
    """Streaming-prefill chunk [t0, t0+C) over the GLOBAL pool tensor.

    Same contract as ``prefill_chunk_paged`` except the pool is the
    whole cluster's [NR, L, NB, bs, K, hd] (DONATED) and the chunk's
    rows can land on ANY rank: wrank/wblk/woff [C] give each row's
    (rank, block, offset) — creditor-striped rows (``PrefixSink``) are
    just rows with a creditor wrank, written by the SAME deferred
    scatter as owner rows (remote DMA under GSPMD when a mesh is
    attached). tables/tails: [NR, MB] / [NR] addressing the written
    prefix [0, t0) on every rank. Returns (logits [1, V], gk, gv,
    k_chunk [L, C, K, hd], v_chunk).
    """
    assert cfg.family in ("dense", "moe"), "only attention archs pool KV"
    backend = resolve_backend(backend)
    C = len(tokens)
    positions = t0 + jnp.arange(C, dtype=jnp.int32)[None]
    valid = (jnp.arange(C, dtype=jnp.int32) < n_valid)[None]
    return _prefill_chunk_global_jit(
        params, jnp.asarray(tokens, jnp.int32)[None], positions, valid,
        jnp.asarray(n_valid - 1, jnp.int32), gk, gv,
        jnp.asarray(tables, jnp.int32), jnp.asarray(tails, jnp.int32),
        jnp.asarray(wrank, jnp.int32), jnp.asarray(wblk, jnp.int32),
        jnp.asarray(woff, jnp.int32), cfg=cfg, backend=backend,
        mesh=mesh, pool_axes=tuple(pool_axes))
